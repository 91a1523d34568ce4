"""Host milliseconds per iteration in the per-iteration in-memory
checkpoint (`Controller._tick_checkpoints`: `engine.get_state` and
`imc.put` for every training machine), timed after the update's device
work is done, so the span holds the device-to-host copies. Traced run
only."""


def read(run):
    n = run.span_count("ckpt_put")
    if not n or not run.iterations:
        return None
    return run.span_total("ckpt_put") * 1e3 / run.iterations
