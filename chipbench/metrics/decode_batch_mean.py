"""Scheduler: requests decoded per engine step, averaged over the window's
steps (``engine.last_step_stats["decode_tokens"]``)."""


def read(run):
    steps = run.host_steps
    if not steps:
        return None
    return sum(s.decode_tokens for s in steps) / len(steps)
