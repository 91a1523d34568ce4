"""Operations and bytes of the benchmark's work, from shapes alone.

Model FLOPs per token and the elements each pipeline stage holds are the
configuration's model module's to count (`models/<name>.py`, found by
`spec.model`); the model modules follow PaLM's appendix B: 6 FLOPs per
weight of every matmul (forward and backward) plus attention's scores
and values, no embedding gather, no work the program recomputes.

The flat Adam update must read the gradient, m, v and the master weight
and write m, v, the master and the parameter, once per element.
"""
from __future__ import annotations

import numpy as np

from chipbench import spec

BYTES = {"float32": 4, "bfloat16": 2}


def update_bytes(cfg: dict, stage: int) -> int:
    """Least HBM bytes of one flat Adam step of a stage: the gradient and
    the parameter in their own dtype, m, v and master read and written
    in float32."""
    elements = spec.model(cfg).stage_elements(cfg, stage)
    return int(sum(n * (2 * BYTES[dt] + 6 * 4)
                   for dt, n in elements.items()))


def iteration_update_bytes(cfg: dict) -> int:
    return sum(update_bytes(cfg, s) for s in range(cfg["pp"]))


def tokens_per_iteration(cfg: dict) -> int:
    return int(np.int64(cfg["global_batch"]) * cfg["seq_len"])
