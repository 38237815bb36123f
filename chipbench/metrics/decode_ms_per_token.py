"""Model step, decode program: device time of the ``jit_serve_decode*``
program executions in the profiled stretch over the tokens of its
``serve.decode`` regions, in milliseconds per token."""
from chipbench import trace_regions


def read(run):
    if run.profile is None:
        return None
    tokens = trace_regions.tokens(trace_regions.named(
        trace_regions.regions(), "serve.decode"))
    seconds = trace_regions.module_seconds(run.profile,
                                           trace_regions.DECODE_PROGRAMS)
    return 1e3 * seconds / tokens if tokens and seconds else None
