"""Pallas TPU blocked RG-LRU linear recurrence.

h_t = a_t * h_{t-1} + x_t over the sequence. Grid: (batch, seq_blocks
sequential, feature_blocks parallel); the hidden state carries across
sequence blocks in VMEM scratch; within a block the recurrence runs as
a vectorized fori_loop over time (features on the lane dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, o_ref, h_ref, *, block_s: int):
    si = pl.program_id(2)      # sequence block: innermost, sequential

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)            # (block_s, bd)
    x = x_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + x[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[0])
    h_ref[0] = h


def rglru_scan(a: jax.Array, x: jax.Array, *, block_s: int = 256,
               block_d: int = 512, interpret: bool = True) -> jax.Array:
    """a, x: (B, S, D) -> h: (B, S, D) with h_t = a_t h_{t-1} + x_t."""
    b, s, d = a.shape
    block_s = min(block_s, s)
    block_d = min(block_d, d)
    assert s % block_s == 0 and d % block_d == 0
    # seq blocks innermost + sequential so the carry in VMEM scratch is
    # valid for one (batch, feature-block) lane at a time.
    grid = (b, d // block_d, s // block_s)
    kernel = functools.partial(_rglru_kernel, block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_d),
                         lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, block_s, block_d),
                         lambda bi, di, si: (bi, si, di)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_d),
                               lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x)
