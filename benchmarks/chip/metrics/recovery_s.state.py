"""Seconds per recovery in moving state: the runtime's `tm:step:xfer`
(a migration's hand-off) and `tm:step:recover` (a failure's restore)
spans inside its `tm:recovery` spans, over the window's number of
`tm:recovery` spans (program spans). A restore only enqueues its copy to
the device; the wait for it falls outside."""

STEPS = ("tm:step:xfer", "tm:step:recover")


def read(run):
    n = len(run.program(("tm:recovery",)))
    return run.program_s(STEPS, "tm:recovery") / n if n else None
