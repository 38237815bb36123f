"""Kernels, FF matmul in prefill: the least time the feed-forward GEMMs of
the profiled steps' prefills need, counting unpadded prompt tokens and
each program's weights once, over the device time of the matmul kernel in
every program that does not decode, in percent."""
from chipbench import trace_reduce
from chipbench.work import dense


def read(run):
    steps = [s for s, _ in run.traced if s.segments]
    if run.profile is None or not steps:
        return None
    decode = trace_reduce.decode_programs(run.profile,
                                          run.conf["serve"]["slots"])
    least = sum(dense.least_seconds(dense.ff(run.conf, sum(
        dense.real_tokens(start, take, pad)
        for _, start, take, pad in s.segments)), run.peaks)
        for s in steps)
    spent = trace_reduce.kernel_seconds(run.profile, "matmul",
                                        decode)["prefill"]
    return 100.0 * least / spent if spent else None
