"""Engine: ``serve.decode`` regions (decode launches) in the profiled
stretch over the ``serve.step`` regions that hold at least one."""
from chipbench import trace_regions


def read(run):
    if run.profile is None:
        return None
    return trace_regions.launches_per_step(trace_regions.regions())
