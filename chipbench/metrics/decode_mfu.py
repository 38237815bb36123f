"""Model step: the model operations of the window's pure decode steps over
those steps' summed host time times the chip's bf16 peak, in percent."""
from chipbench.work import dense


def read(run):
    steps = [s for s in run.host_steps
             if s.decode_ctx and not s.prefill_tokens]
    seconds = sum(s.end - s.start for s in steps)
    if not seconds:
        return None
    flops = sum(dense.decode_step_flops(run.conf, s.decode_ctx)
                for s in steps)
    return 100.0 * flops / (seconds * run.peaks["flops_bf16_per_s"])
