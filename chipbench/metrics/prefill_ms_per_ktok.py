"""Model step, prefill programs: device time of the ``jit_serve_pack*``,
``jit_serve_chunk*`` and ``jit_serve_prefill*`` program executions in the
profiled stretch over the unpadded prompt tokens of its ``serve.prefill``
regions, in milliseconds per thousand tokens."""
from chipbench import trace_regions


def read(run):
    if run.profile is None:
        return None
    tokens = trace_regions.tokens(trace_regions.named(
        trace_regions.regions(), "serve.prefill"))
    seconds = trace_regions.module_seconds(run.profile,
                                           trace_regions.PREFILL_PROGRAMS)
    return 1e6 * seconds / tokens if tokens and seconds else None
