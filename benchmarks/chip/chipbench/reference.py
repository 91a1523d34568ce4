"""Plain reference of the benchmark's training jobs.

Written in straightforward `jax.numpy`, with nothing imported from the
runtime. The architecture is the configuration's model module
(`models/<name>.py`, found by `spec.model`): its weights, drawn from the
seed with the same `jax.random` calls the model's initialiser makes,
its loss of one sequence, and which leaves each pipeline stage holds.
What every architecture shares is here: next-token data from a copy of
the seeded synthetic stream, rounding to a narrower format, the small
ops a model may use (`_rms`, `_rope`), and AdamW with a linear warm-up
and gradient clipping by each pipeline stage's norm. Weights and tokens
are made here, not taken from the program.

The reference follows the first `steps` optimizer steps of a job on the
whole global batch, one sequence at a time ("blocks of rows"), with
every float32 matmul at `highest` precision. It returns, per step, the
loss; per (stage, leaf), the norm of the first step's clipped gradient;
and Adam's master weights before the first and after the last step.

`precision` selects the arithmetic: "reference" is the configuration's
stated precision (float32 math; the leaves the model stores in
`param_dtype` rounded to it), and "control" is the next precision down,
as the configuration's `control` entry names it (those leaves rounded to
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

from chipbench import spec


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
def stage_slices(cfg: dict) -> List[Tuple[int, str, slice]]:
    """(stage, leaf, layer slice) of every leaf as a pipeline stage holds
    it, from the model's `stage_leaves`."""
    return [(s, n, slice(None) if sl is None else sl)
            for s, held in enumerate(spec.model(cfg).stage_leaves(cfg))
            for n, sl in held.items()]


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
    """The weights the forward pass sees: the leaves kept in
    `param_dtype` rounded to their stored precision, then everything
    cast to the compute dtype."""
    stack_fmt, compute_dt, _, _ = _arith(cfg, precision)
    stored = spec.model(cfg).stored_in_param_dtype
    return {n: (round_to(x, stack_fmt) if stored(n) else x)
            .astype(compute_dt) for n, x in master.items()}


def make_grad_fn(cfg: dict, precision: str):
    _, _, operands, prec = _arith(cfg, precision)
    sequence_loss = spec.model(cfg).sequence_loss

    def grad_fn(master, tokens):
        def loss(m):
            return sequence_loss(_effective(m, cfg, precision), tokens,
                                 cfg, prec, operands)
        val, g = jax.value_and_grad(loss)(master)
        return val, jax.tree.map(lambda x: x.astype(jnp.float32), g)

    return jax.jit(grad_fn)


# ----------------------------------------------------------------- adam
def clip_by_stage(grads, cfg: dict):
    """Scale each stage's gradients by min(1, clip / that stage's norm)."""
    clip = cfg["optimizer"]["grad_clip"]
    part = lambda x, sl: x if sl is None else x[sl]
    out = {n: [] for n in grads}
    for held in spec.model(cfg).stage_leaves(cfg):
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
    w0 = spec.model(cfg).init_weights(cfg, seed)
    stack_dt = cfg["param_dtype"]
    stored = spec.model(cfg).stored_in_param_dtype
    master0 = {n: round_to(x, stack_dt) if stored(n) else x
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
