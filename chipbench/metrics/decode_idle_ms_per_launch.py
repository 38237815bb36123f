"""Engine, host turnaround: the time inside the profiled stretch's
``serve.decode`` regions in which no operation ran on the device, over
the number of those regions, in milliseconds per launch."""
from chipbench import trace_regions


def read(run):
    if run.profile is None or not run.profile.ops:
        return None
    decodes = trace_regions.named(trace_regions.regions(), "serve.decode")
    if not decodes:
        return None
    return 1e3 * trace_regions.idle_within(run.profile, decodes) / len(
        decodes)
