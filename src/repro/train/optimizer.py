"""AdamW with ZeRO-1 distributed-optimizer sharding.

Parameters stay bf16 sharded by the model's TP rules (replicated over
DP); the f32 master copy and both moments are additionally sharded over
the DP axes (first divisible unsharded dim), which is exactly the
Megatron "distributed optimizer" the paper's GPT-20B/39.1B runs enable.
GSPMD materializes the implied reduce-scatter (grads -> moment shards)
and all-gather (master -> bf16 params).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class AdamCfg:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100


def init_opt_state(params) -> dict:
    f32 = lambda p: p.astype(jnp.float32)
    return {
        "m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "master": jax.tree.map(f32, params),
        "step": jnp.zeros((), jnp.int32),
    }


def _schedule(cfg: AdamCfg, step):
    warm = jnp.minimum(step.astype(jnp.float32) / max(cfg.warmup_steps, 1),
                       1.0)
    return cfg.lr * warm


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adam_update(grads, opt_state: dict, cfg: AdamCfg,
                param_dtype=jnp.bfloat16):
    """Returns (new_params, new_opt_state, stats).

    param_dtype=None preserves each leaf's own dtype (mixed-precision
    stages: bf16 stack weights next to fp32 norms/embeddings)."""
    step = opt_state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
        if cfg.grad_clip else 1.0
    lr = _schedule(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(g, m, v, master):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay:
            delta = delta + cfg.weight_decay * master
        master = master - lr * delta
        return m, v, master

    flat_g, tdef = jax.tree.flatten(grads)
    flat_m = tdef.flatten_up_to(opt_state["m"])
    flat_v = tdef.flatten_up_to(opt_state["v"])
    flat_ma = tdef.flatten_up_to(opt_state["master"])
    out = [upd(g, m, v, ma)
           for g, m, v, ma in zip(flat_g, flat_m, flat_v, flat_ma)]
    new_m = tdef.unflatten([o[0] for o in out])
    new_v = tdef.unflatten([o[1] for o in out])
    new_master = tdef.unflatten([o[2] for o in out])
    if param_dtype is None:         # keep each leaf's own precision
        new_params = jax.tree.map(lambda x, g: x.astype(g.dtype),
                                  new_master, grads)
    else:
        new_params = jax.tree.map(lambda x: x.astype(param_dtype),
                                  new_master)
    new_state = {"m": new_m, "v": new_v, "master": new_master,
                 "step": step}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}


# ------------------------------------------------ fully-flat hot path
# FFTrainer-style (arXiv 2512.03644): the optimizer state lives as flat
# vectors aligned to the gradient bucket's segment-major element space,
# so the update is pure vector arithmetic with no unflatten/flatten
# inside jit and leaver->joiner state packing is a memcpy.  Every step
# below mirrors adam_update's per-leaf arithmetic elementwise (same op
# order, same scalar schedule, per-leaf norm partials in leaf order),
# which is what keeps the two paths bitwise identical.

def init_flat_opt_state(spec, params) -> dict:
    """Flat m/v/master vectors over `spec`'s master space."""
    segs = spec.flatten(params)
    master = (jnp.concatenate([s.astype(jnp.float32) for s in segs])
              if segs else jnp.zeros((0,), jnp.float32))
    return {
        "m": jnp.zeros((spec.size,), jnp.float32),
        "v": jnp.zeros((spec.size,), jnp.float32),
        "master": master,
        "step": jnp.zeros((), jnp.int32),
    }


def params_from_master(spec, master) -> tuple:
    """A stage's param buckets from its flat fp32 master vector: each
    bucket's slice of the master space, cast to the bucket's dtype.
    The flat update makes its new buckets with it, and a restore from
    the in-memory checkpoint (which holds no params) rebuilds them with
    it, so the two are bitwise the same."""
    return tuple(master[lo:hi].astype(seg.dtype)
                 for seg, (lo, hi) in zip(spec.segments,
                                          spec.segment_bounds()))


def adam_update_flat(spec, grad_segs, opt_state: dict, cfg: AdamCfg):
    """AdamW over per-dtype gradient buckets; returns
    (new_param_segments, new_opt_state, stats).

    grad_segs are `spec.flatten` outputs (already averaged). The norm
    is accumulated from per-leaf partials in the original leaf order —
    reshaped to the leaf shapes — because that is exactly what
    global_norm does on the unflattened tree; everything else is
    elementwise and runs on the whole vector at once."""
    step = opt_state["step"] + 1
    views = [jnp.reshape(grad_segs[si][o:o + n], sh)
             for si, o, n, sh in spec.leaf_views()]
    gnorm = global_norm(views)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
        if cfg.grad_clip else 1.0
    lr = _schedule(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    g = (jnp.concatenate([s.astype(jnp.float32) for s in grad_segs])
         if grad_segs else jnp.zeros((0,), jnp.float32)) * scale
    m = cfg.b1 * opt_state["m"] + (1 - cfg.b1) * g
    v = cfg.b2 * opt_state["v"] + (1 - cfg.b2) * g * g
    mhat = m / b1c
    vhat = v / b2c
    delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
    master = opt_state["master"]
    if cfg.weight_decay:
        delta = delta + cfg.weight_decay * master
    master = master - lr * delta
    new_segs = params_from_master(spec, master)
    new_state = {"m": m, "v": v, "master": master, "step": step}
    return new_segs, new_state, {"grad_norm": gnorm, "lr": lr}


# -------------------------------------------------- ZeRO-1 sharding rule
def zero1_pspec(pspec: P, shape, mesh: Mesh) -> P:
    """Extend a param PartitionSpec with DP sharding on the first
    unsharded dim divisible by the DP extent (ZeRO-1)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    if not dp_axes:
        return pspec
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    used = {a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    if used & set(dp_axes):          # already DP-sharded (e.g. FSDP)
        return P(*entries)
    for cand in (dp_axes, dp_axes[-1:]):       # full DP, else 'data' only
        dp = 1
        for a in cand:
            dp *= sizes[a]
        for i, (e, s) in enumerate(zip(entries, shape)):
            if e is None and s % dp == 0 and s >= dp:
                entries[i] = cand if len(cand) > 1 else cand[0]
                return P(*entries)
    return P(*entries)


def opt_shardings(param_specs, param_shardings, mesh: Mesh) -> dict:
    """Shardings pytree for init_opt_state's output.

    param_specs: pytree of ShapeDtypeStruct; param_shardings: matching
    pytree of NamedSharding (leaves, so tree.map pairs them safely).
    """
    z1 = jax.tree.map(
        lambda spec, sh: NamedSharding(
            mesh, zero1_pspec(sh.spec, spec.shape, mesh)),
        param_specs, param_shardings)
    return {
        "m": z1, "v": z1, "master": z1,
        "step": NamedSharding(mesh, P()),
    }
