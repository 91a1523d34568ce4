"""Operations and bytes of the benchmark's work, from shapes alone.

Model FLOPs per token follow PaLM's appendix B: 6 per weight of every
matmul (forward and backward) plus 12 * layers * heads * head_dim *
sequence for attention's scores and values. The embedding gather is no
matmul, and work recomputed by the program (stage 0 runs its forward
again inside its backward) does not count.

The flat Adam update must read the gradient, m, v and the master weight
and write m, v, the master and the parameter, once per element.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

BYTES = {"float32": 4, "bfloat16": 2}


def head_dim(cfg: dict) -> int:
    return cfg["head_dim"] or cfg["d_model"] // cfg["num_heads"]


def layer_params(cfg: dict) -> Dict[str, int]:
    d, f, k = cfg["d_model"], cfg["d_ff"], head_dim(cfg)
    h, kv = cfg["num_heads"], cfg["num_kv_heads"]
    return {"matmul": d * h * k * 2 + d * kv * k * 2 + 3 * d * f,
            "norm": 2 * d}


def model_flops_per_token(cfg: dict) -> float:
    L, d, v = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"]
    n_matmul = L * layer_params(cfg)["matmul"] + d * v
    attn = 12 * L * cfg["num_heads"] * head_dim(cfg) * cfg["seq_len"]
    return 6.0 * n_matmul + attn


def stage_elements(cfg: dict, stage: int) -> Dict[str, int]:
    """{dtype: elements} of one pipeline stage's parameters: its layers
    in `param_dtype`, the embedding (first stage) and the final norm and
    head (last stage) in float32."""
    per = cfg["num_layers"] // cfg["pp"]
    lp = layer_params(cfg)
    out = {cfg["param_dtype"]: per * (lp["matmul"] + lp["norm"])}
    f32 = 0
    if stage == 0:
        f32 += cfg["vocab_size"] * cfg["d_model"]
    if stage == cfg["pp"] - 1:
        f32 += cfg["d_model"] + cfg["d_model"] * cfg["vocab_size"]
    out["float32"] = out.get("float32", 0) + f32
    return out


def update_bytes(cfg: dict, stage: int) -> int:
    """Least HBM bytes of one flat Adam step of a stage: the gradient and
    the parameter in their own dtype, m, v and master read and written
    in float32."""
    return int(sum(n * (2 * BYTES[dt] + 6 * 4)
                   for dt, n in stage_elements(cfg, stage).items()))


def iteration_update_bytes(cfg: dict) -> int:
    return sum(update_bytes(cfg, s) for s in range(cfg["pp"]))


def tokens_per_iteration(cfg: dict) -> int:
    return int(np.int64(cfg["global_batch"]) * cfg["seq_len"])
