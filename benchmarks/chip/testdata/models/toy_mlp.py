"""A toy architecture for the harness's tests: embedding, residual MLP
layers (RMSNorm, one matmul up, GeLU, one down), final norm and head.
It stands for a new model module: the tests resolve it by the `model`
key of a configuration and run the reference, the comparison and the
counts on it, with no other harness file changed. The runtime has no
such model, so `arch` refuses."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _cached, _key, _rms, round_to

LAYER_LEAVES = ("ln", "w_up", "w_down")


def arch(cfg: dict):
    raise NotImplementedError("the runtime has no toy_mlp model")


def init_weights(cfg: dict, seed: int):
    d, f, v, L = (cfg["d_model"], cfg["d_ff"], cfg["vocab_size"],
                  cfg["num_layers"])

    def make(key):
        k = jax.random.split(key, 4)
        return {"embed": jax.random.normal(k[0], (v, d)) * 0.02,
                "ln": jnp.ones((L, d)),
                "w_up": jax.random.normal(k[1], (L, d, f)) * d ** -0.5,
                "w_down": jax.random.normal(k[2], (L, f, d)) * f ** -0.5,
                "final_ln": jnp.ones((d,)),
                "head": jax.random.normal(k[3], (d, v)) * 0.02}

    return _cached(("init", _key(cfg)), lambda: jax.jit(make))(
        jax.random.PRNGKey(seed))


def stored_in_param_dtype(leaf: str) -> bool:
    return leaf in LAYER_LEAVES


def stage_leaves(cfg: dict):
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


def sequence_loss(w, tokens, cfg: dict, prec, operands: str = "float32"):
    ein = lambda spec, a, b: jnp.einsum(spec, round_to(a, operands),
                                        round_to(b, operands),
                                        precision=prec)
    x = w["embed"][tokens]
    for i in range(cfg["num_layers"]):
        h = _rms(x, w["ln"][i], cfg["norm_eps"])
        x = x + ein("sf,fd->sd", jax.nn.gelu(ein("sd,df->sf", h,
                                                 w["w_up"][i])),
                    w["w_down"][i])
    x = _rms(x, w["final_ln"], cfg["norm_eps"])
    logits = ein("sd,dv->sv", x, w["head"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits[:-1], -1)
    true = jnp.take_along_axis(logits[:-1], tokens[1:, None], -1)[:, 0]
    return jnp.mean(lse - true)


def model_flops_per_token(cfg: dict) -> float:
    d, f = cfg["d_model"], cfg["d_ff"]
    return 6.0 * (cfg["num_layers"] * 2 * d * f + d * cfg["vocab_size"])


def stage_elements(cfg: dict, stage: int):
    d, per = cfg["d_model"], cfg["num_layers"] // cfg["pp"]
    out = {cfg["param_dtype"]: per * (2 * d * cfg["d_ff"] + d),
           "float32": 0}
    if stage == 0:
        out["float32"] += cfg["vocab_size"] * d
    if stage == cfg["pp"] - 1:
        out["float32"] += d + d * cfg["vocab_size"]
    return out
