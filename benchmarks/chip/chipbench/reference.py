"""Plain reference of the benchmark's training jobs.

Written from the model's equations in straightforward `jax.numpy`, with
nothing imported from the runtime: the decoder block of the repo's GPT
family (RMSNorm, rotary positions, causal softmax attention, SwiGLU
MLP, untied head), next-token cross entropy, and AdamW with a linear
warm-up and gradient clipping by each pipeline stage's norm. Weights
are drawn from the seed with the same `jax.random` calls the model's
initialiser makes, and tokens come from a copy of the seeded synthetic
stream; both are made here, not taken from the program.

The reference follows the first `steps` optimizer steps of a job on the
whole global batch, one sequence at a time ("blocks of rows"), with
every float32 matmul at `highest` precision. It returns, per step, the
loss; per (stage, leaf), the norm of the first step's clipped gradient;
and Adam's master weights before the first and after the last step.

`precision` selects the arithmetic: "reference" is the configuration's
stated precision (float32 math; transformer-block weights rounded to
`param_dtype`), and "control" is the next precision down, as the
configuration's `control` entry names it (block weights rounded to
`stack_weights`, every matmul's operands to `matmul_inputs`, all other
arithmetic in the `compute` dtype).
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1", "attn.wq", "attn.wk", "attn.wv", "attn.wo", "ln2",
                "mlp.w_gate", "mlp.w_up", "mlp.w_down")


# ---------------------------------------------------------------- data
def stream_tokens(vocab: int, batch: int, seq: int, seed: int,
                  step: int) -> np.ndarray:
    """(batch, seq) int32 tokens of one step: a Zipf(1.1) marginal where
    half of the positions copy a fixed successor of the previous token.
    The stream's own seed is the job's seed + 77."""
    sseed = seed + 77
    v = vocab
    ranks = np.arange(1, v + 1, dtype=np.float64)
    p = 1.0 / ranks ** 1.1
    p = p / p.sum()
    succ = np.random.default_rng(sseed).integers(0, v, size=v,
                                                 dtype=np.int64)
    rng = np.random.default_rng((sseed, step))
    draws = rng.choice(v, size=(batch, seq), p=p)
    follow = rng.random((batch, seq)) < 0.5
    toks = draws.copy()
    for t in range(1, seq):
        toks[:, t] = np.where(follow[:, t], succ[toks[:, t - 1]],
                              draws[:, t])
    return toks.astype(np.int32)


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


def stage_slices(cfg: dict) -> List[Tuple[int, str, slice]]:
    """(stage, leaf, layer slice) of every leaf as a pipeline stage holds
    it: each stage's layers stacked, the embedding on the first stage,
    the final norm and head on the last."""
    per = cfg["num_layers"] // cfg["pp"]
    out = [(0, "embed", slice(None))]
    for s in range(cfg["pp"]):
        out += [(s, n, slice(s * per, (s + 1) * per)) for n in LAYER_LEAVES]
    last = cfg["pp"] - 1
    return out + [(last, "final_ln", slice(None)),
                  (last, "head", slice(None))]


# --------------------------------------------------------------- model
FORMATS = {"bfloat16": (8, 7), "float8_e4m3": (4, 3)}   # exponent, mantissa


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def round_to(x, fmt: str):
    """x's values rounded to `fmt` ("float32" leaves them), in x's own
    dtype; derivatives pass through unrounded. `reduce_precision` is
    used because XLA may drop a cast to a narrower type and back. An
    8-bit float takes one scale per tensor, as an fp8 path stores one
    (IEEE-style e4m3: largest value 240)."""
    if fmt == "float32":
        return x
    ebits, mbits = FORMATS[fmt]
    if ebits == 8:
        return jax.lax.reduce_precision(x, ebits, mbits)
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = (jnp.maximum(amax, 1e-30) / 240.0).astype(x.dtype)
    return jax.lax.reduce_precision(x / scale, ebits, mbits) * scale


@round_to.defjvp
def _round_to_jvp(fmt, primals, tangents):
    return round_to(primals[0], fmt), tangents[0]


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return x * scale.astype(x.dtype) * g


def _rope(x, theta):
    """x: (S, H, K); rotation of the two halves of each head."""
    s, _, k = x.shape
    half = k // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


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


def _arith(cfg: dict, precision: str):
    """(format of the block weights, compute dtype, format of every
    matmul's operands, matmul precision)."""
    if precision == "reference":
        return (cfg["param_dtype"], "float32", "float32",
                jax.lax.Precision.HIGHEST)
    ctl = cfg["control"]
    return (ctl["stack_weights"], ctl["compute"], ctl["matmul_inputs"],
            jax.lax.Precision.DEFAULT)


def _effective(master, cfg: dict, precision: str):
    """The weights the forward pass sees: block weights rounded to their
    stored precision, then everything cast to the compute dtype."""
    stack_fmt, compute_dt, _, _ = _arith(cfg, precision)
    return {n: (round_to(x, stack_fmt) if n in LAYER_LEAVES else x)
            .astype(compute_dt) for n, x in master.items()}


def make_grad_fn(cfg: dict, precision: str):
    _, _, operands, prec = _arith(cfg, precision)

    def grad_fn(master, tokens):
        def loss(m):
            return sequence_loss(_effective(m, cfg, precision), tokens,
                                 cfg, prec, operands)
        val, g = jax.value_and_grad(loss)(master)
        return val, jax.tree.map(lambda x: x.astype(jnp.float32), g)

    return jax.jit(grad_fn)


# ----------------------------------------------------------------- adam
def _stage_leaves(cfg: dict):
    """Per stage, {leaf: layer slice, or None for the whole leaf}."""
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


def clip_by_stage(grads, cfg: dict):
    """Scale each stage's gradients by min(1, clip / that stage's norm)."""
    clip = cfg["optimizer"]["grad_clip"]
    part = lambda x, sl: x if sl is None else x[sl]
    out = {n: [] for n in grads}
    for held in _stage_leaves(cfg):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(part(grads[n], sl)))
                            for n, sl in held.items()))
        fac = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
        for n, sl in held.items():
            out[n].append(part(grads[n], sl) * fac)
    # the stages hold disjoint runs of layers, in order
    return {n: parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            for n, parts in out.items()}


def adam_step(master, m, v, g, step: int, opt: dict):
    """One AdamW step (step counts from 1), elementwise per leaf."""
    lr = opt["lr"] * min(step / max(opt["warmup_steps"], 1), 1.0)
    b1, b2 = opt["b1"], opt["b2"]
    b1c, b2c = 1.0 - b1 ** step, 1.0 - b2 ** step
    new = {}
    for n in master:
        mm = b1 * m[n] + (1 - b1) * g[n]
        vv = b2 * v[n] + (1 - b2) * g[n] * g[n]
        delta = (mm / b1c) / (jnp.sqrt(vv / b2c) + opt["eps"]) \
            + opt["weight_decay"] * master[n]
        new[n] = (master[n] - lr * delta, mm, vv)
    return ({n: t[0] for n, t in new.items()},
            {n: t[1] for n, t in new.items()},
            {n: t[2] for n, t in new.items()})


# ------------------------------------------------------------ a follow
_JITTED: Dict[tuple, object] = {}


def _key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def _cached(key: tuple, make):
    """One jitted program per configuration and purpose, so that a
    process following many seeds traces each once."""
    if key not in _JITTED:
        _JITTED[key] = make()
    return _JITTED[key]


def follow(cfg: dict, seed: int, steps: int, precision: str = "reference",
           rows: Optional[Sequence[int]] = None) -> dict:
    """Follow the job's first `steps` optimizer steps on the batch's
    `rows` (all of them by default), the loss and gradient being the
    mean over those rows.

    Returns {"losses": [..], "grad_norms": {(s, leaf): norm}, "grads0":
    step 0's clipped gradient, "master0": weights before step 0,
    "master": weights after the last step}; the arrays are float32 dicts
    on the device, layer leaves stacked over all layers."""
    w0 = init_weights(cfg, seed)
    stack_dt = cfg["param_dtype"]
    master0 = {n: round_to(x, stack_dt) if n in LAYER_LEAVES else x
               for n, x in w0.items()}
    key, opt = _key(cfg), cfg["optimizer"]
    grad_fn = _cached(("grad", key, precision),
                      lambda: make_grad_fn(cfg, precision))
    clip = _cached(("clip", key), lambda: jax.jit(
        lambda g, n: clip_by_stage({k: x / n for k, x in g.items()}, cfg)))
    update = _cached(("update", key), lambda: jax.jit(
        lambda ma, m, v, g, t: adam_step(ma, m, v, g, t, opt),
        static_argnums=4))
    add = _cached(("add",), lambda: jax.jit(
        lambda a, b: jax.tree.map(jnp.add, a, b)))
    master = master0
    m = {n: jnp.zeros_like(x) for n, x in master.items()}
    v = {n: jnp.zeros_like(x) for n, x in master.items()}
    losses, grad_norms, grads0 = [], {}, None
    rows = list(range(cfg["global_batch"])) if rows is None else list(rows)
    for t in range(steps):
        toks = stream_tokens(cfg["vocab_size"], cfg["global_batch"],
                             cfg["seq_len"], seed, t)
        total, gsum = 0.0, None
        for r in rows:
            val, g = grad_fn(master, jnp.asarray(toks[r]))
            total += float(val)
            gsum = g if gsum is None else add(gsum, g)
        grads = clip(gsum, float(len(rows)))
        del gsum
        losses.append(total / len(rows))
        if t == 0:
            grads0 = grads
            for s, name, sl in stage_slices(cfg):
                grad_norms[(s, name)] = float(jnp.sqrt(jnp.sum(
                    jnp.square(grads[name][sl]))))
        master, m, v = update(master, m, v, grads, t + 1)
    return {"losses": losses, "grad_norms": grad_norms, "grads0": grads0,
            "master0": master0, "master": master}
