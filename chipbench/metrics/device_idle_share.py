"""Device: the share of the time inside the profiled ``engine.step`` spans
in which no operation ran on the device, in percent."""
from chipbench import trace_reduce


def read(run):
    if run.profile is None:
        return None
    share = trace_reduce.idle_share(run.profile)
    return None if share is None else 100.0 * share
