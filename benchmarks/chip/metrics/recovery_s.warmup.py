"""Seconds per recovery in the recovery runs' `warmup` steps: the
runtime's `tm:step:warmup` spans inside its `tm:recovery` spans, over
the window's number of `tm:recovery` spans (program spans)."""


def read(run):
    n = len(run.program(("tm:recovery",)))
    return run.program_s(("tm:step:warmup",), "tm:recovery") / n if n \
        else None
