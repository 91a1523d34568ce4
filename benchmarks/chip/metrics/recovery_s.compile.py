"""Seconds per recovery in role compiles: the runtime's
`tm:compile_role` spans inside its `tm:recovery` spans (in a migration's
warm-up or a failure's promotion), over the window's number of
`tm:recovery` spans (program spans)."""


def read(run):
    n = len(run.program(("tm:recovery",)))
    return run.program_s(("tm:compile_role",), "tm:recovery") / n if n \
        else None
