"""Model FLOP/s utilization of the whole step, in %: model FLOPs per
token (the configuration's model module, PaLM's convention: no
recomputation, no embedding gather) times the traced run's own tokens
per second, over the chip's bf16 peak (peaks.json) times the chips
used."""
from chipbench import spec


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    peak = run.peaks()["bf16_flops"] * len(run.trace.devices)
    return 100.0 * spec.model(run.cfg).model_flops_per_token(run.cfg) \
        * run.tokens_per_s / peak
