"""The repo's GPT family decoder block, as the benchmark models it.

A model module of the benchmark (see README): a configuration file
without a `model` key is run as this one. It holds what is particular
to the architecture, written from the model's equations in
straightforward `jax.numpy` with nothing imported from the runtime but
`ArchConfig` in `arch()`:

- `arch(cfg)`: the runtime's `ArchConfig` of the configuration;
- `init_weights(cfg, seed)`: float32 weights drawn from the seed with
  the same `jax.random` calls the model's initialiser makes;
- `sequence_loss(w, tokens, cfg, prec, operands)`: RMSNorm, rotary
  positions, causal softmax attention, SwiGLU MLP, untied head and
  next-token cross entropy of one sequence;
- `stage_leaves(cfg)`: which leaves, and which of their layers, each
  pipeline stage holds;
- `stored_in_param_dtype(leaf)`: whether a leaf is kept in the
  configuration's `param_dtype` (the transformer-block weights) or in
  float32;
- `model_flops_per_token(cfg)` and `stage_elements(cfg, stage)`: the
  counts that `chipbench.flops` reports.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from chipbench.reference import _cached, _key, _rms, _rope, round_to

LAYER_LEAVES = ("ln1", "attn.wq", "attn.wk", "attn.wv", "attn.wo", "ln2",
                "mlp.w_gate", "mlp.w_up", "mlp.w_down")
ARCH_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
             "head_dim", "d_ff", "vocab_size", "rope_theta", "norm_eps",
             "tie_embeddings")


def arch(cfg: dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(name=cfg["name"], family="dense",
                      block_pattern=tuple(cfg["block_pattern"]),
                      **{k: cfg[k] for k in ARCH_KEYS})


# ------------------------------------------------------------- weights
def init_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """float32 weights from the seed; layer leaves stacked over layers."""
    d, v, f = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
    h, kv = cfg["num_heads"], cfg["num_kv_heads"]
    k = cfg["head_dim"] or d // h
    L = cfg["num_layers"]

    def scaled(key, *shape):
        return jax.random.normal(key, shape) * (shape[0] ** -0.5)

    def layer(key):
        k_attn, k_mlp, _ = jax.random.split(key, 3)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)
        return {"ln1": jnp.ones((d,)),
                "attn.wq": scaled(ka[0], d, h, k),
                "attn.wk": scaled(ka[1], d, kv, k),
                "attn.wv": scaled(ka[2], d, kv, k),
                "attn.wo": scaled(ka[3], h, k, d),
                "ln2": jnp.ones((d,)),
                "mlp.w_gate": scaled(km[0], d, f),
                "mlp.w_up": scaled(km[1], d, f),
                "mlp.w_down": scaled(km[2], f, d)}

    def make(key):
        keys = jax.random.split(key, 6)
        layer_keys = jax.random.split(keys[1], L + 1)
        layers = [layer(layer_keys[i]) for i in range(L)]
        w = {n: jnp.stack([ly[n] for ly in layers]) for n in LAYER_LEAVES}
        w["embed"] = jax.random.normal(keys[0], (v, d)) * 0.02
        w["final_ln"] = jnp.ones((d,))
        w["head"] = jax.random.normal(keys[2], (d, v)) * 0.02
        return w

    return _cached(("init", _key(cfg)), lambda: jax.jit(make))(
        jax.random.PRNGKey(seed))


def stored_in_param_dtype(leaf: str) -> bool:
    return leaf in LAYER_LEAVES


def stage_leaves(cfg: dict) -> List[Dict[str, Optional[slice]]]:
    """Per stage, {leaf: layer slice, or None for the whole leaf}: each
    stage's layers stacked, the embedding on the first stage, the final
    norm and head on the last."""
    per = cfg["num_layers"] // cfg["pp"]
    out = []
    for s in range(cfg["pp"]):
        m = {n: slice(s * per, (s + 1) * per) for n in LAYER_LEAVES}
        if s == 0:
            m["embed"] = None
        if s == cfg["pp"] - 1:
            m["final_ln"] = m["head"] = None
        out.append(m)
    return out


# --------------------------------------------------------------- model
def sequence_loss(w, tokens, cfg: dict, prec, operands: str = "float32"):
    """Mean next-token cross entropy of one sequence (S,); every matmul's
    operands are rounded to `operands` first."""
    dt = w["embed"].dtype
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    ein = lambda spec, a, b: jnp.einsum(spec, round_to(a, operands),
                                        round_to(b, operands),
                                        precision=prec)
    x = w["embed"][tokens]
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg["num_layers"]):
        ly = {n: w[n][i] for n in LAYER_LEAVES}
        h = _rms(x, ly["ln1"], eps)
        q = _rope(ein("sd,dhk->shk", h, ly["attn.wq"]), theta)
        k = _rope(ein("sd,dhk->shk", h, ly["attn.wk"]), theta)
        v = ein("sd,dhk->shk", h, ly["attn.wv"])
        q = q * jnp.asarray(q.shape[-1] ** -0.5, dt)
        logits = ein("shk,thk->hst", q, k)
        logits = jnp.where(causal[None], logits, jnp.asarray(-1e30, dt))
        p = jax.nn.softmax(logits, axis=-1)
        o = ein("hst,thk->shk", p, v)
        x = x + ein("shk,hkd->sd", o, ly["attn.wo"])
        h2 = _rms(x, ly["ln2"], eps)
        gate = jax.nn.silu(ein("sd,df->sf", h2, ly["mlp.w_gate"]))
        x = x + ein("sf,fd->sd", gate * ein("sd,df->sf", h2,
                                            ly["mlp.w_up"]),
                    ly["mlp.w_down"])
    x = _rms(x, w["final_ln"], eps)
    logits = ein("sd,dv->sv", x, w["head"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits[:-1], -1)
    true = jnp.take_along_axis(logits[:-1], tokens[1:, None], -1)[:, 0]
    return jnp.mean(lse - true)


# -------------------------------------------------------------- counts
# Model FLOPs per token follow PaLM's appendix B: 6 per weight of every
# matmul (forward and backward) plus 12 * layers * heads * head_dim *
# sequence for attention's scores and values. The embedding gather is no
# matmul, and work recomputed by the program (stage 0 runs its forward
# again inside its backward) does not count.
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
