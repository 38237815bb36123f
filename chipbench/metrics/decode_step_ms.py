"""Engine: median host time of the window's ``engine.step()`` calls that
ran no prefill tokens (pure decode steps), in milliseconds."""
import statistics


def read(run):
    times = [s.end - s.start for s in run.host_steps
             if s.decode_tokens and not s.prefill_tokens]
    if not times:
        return None
    return 1e3 * statistics.median(times)
