"""1 - (union of the device's operation intervals inside the runtime's
`tm:train_iteration` spans) / (those spans' length), averaged over the
devices (device trace, program spans on its clock). The iteration span
holds the stage programs and the enqueue of the reduce and update; the
checkpoint after it is outside."""
from chipbench import trace_reduce

SPAN = "tm:train_iteration"


def read(run):
    if run.trace is None:
        return None
    busy, length = trace_reduce.busy_within(run.trace, SPAN)
    return 1.0 - busy / length if length else None
