"""Kernels, FF matmul in decode: the least time the feed-forward GEMMs of
the profiled steps' decodes need (each step's weights read once, its
decoded requests' rows computed) over the device time of the matmul
kernel in the decode programs, in percent. Decode programs are those whose
FF matmul takes at most the engine's decode slots of rows."""
from chipbench import trace_reduce
from chipbench.work import dense


def read(run):
    steps = [s for s, _ in run.traced if s.decode_ctx]
    if run.profile is None or not steps:
        return None
    decode = trace_reduce.decode_programs(run.profile,
                                          run.conf["serve"]["slots"])
    least = sum(dense.least_seconds(dense.ff(run.conf, len(s.decode_ctx)),
                                    run.peaks) for s in steps)
    spent = trace_reduce.kernel_seconds(run.profile, "matmul",
                                        decode)["decode"]
    return 100.0 * least / spent if spent else None
