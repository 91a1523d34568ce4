"""1 - (union of the device's operation intervals) / (traced window),
averaged over the devices (device trace)."""
from chipbench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 1.0 - trace_reduce.busy_s(run.trace) / trace_reduce.window_s(
        run.trace)
