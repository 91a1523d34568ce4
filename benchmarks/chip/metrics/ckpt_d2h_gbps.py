"""GB/s (1e9 bytes) of the per-iteration in-memory checkpoint's copies to
the host: the bytes that cross to the host in each `tm:ckpt` span over
the seconds of the `tm:get_state` spans inside it (program spans and the
runtime's `bytes.d2h` counter; traced run, the update's device work
waited for before each tick).

After an update the DP replicas of a stage hold the same state arrays,
and `jax.device_get` copies an array once and hands the cached host copy
to the next call, while `bytes.d2h` counts every call. So a tick's bytes
are taken as one state a pipeline stage: the bytes its calls counted,
times the stages over the calls (no more than all of them)."""


def read(run):
    stages = run.cfg["pp"]
    calls = run.program(("tm:get_state",), inside="tm:ckpt")
    counts = [c for c in run.program_counts if c.name == "bytes.d2h"]
    copied = sec = 0.0
    for tick in run.program(("tm:ckpt",)):
        mine = [sp for sp in calls if tick.start <= sp.start <= tick.end]
        if not mine:
            continue
        counted = sum(c.n for c in counts if tick.start <= c.t <= tick.end)
        copied += counted * min(1.0, stages / len(mine))
        sec += sum(sp.end - sp.start for sp in mine)
    return copied / 1e9 / sec if sec else None
