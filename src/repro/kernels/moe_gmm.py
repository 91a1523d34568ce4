"""Pallas TPU grouped expert matmul (MoE hot path).

Per-expert GEMM over capacity-packed buffers: x (E, C, D) @ w (E, D, F)
-> (E, C, F), tiled (block_c x block_f) with a sequential reduction over
D blocks accumulated in VMEM scratch. MXU-aligned 128 tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0].astype(jnp.float32),
                            w_ref[0].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, *, block_c: int = 128,
                   block_f: int = 128, block_d: int = 256,
                   interpret: bool = True) -> jax.Array:
    """x: (E, C, D), w: (E, D, F) -> (E, C, F)."""
    e, c, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert c % block_c == 0 and f % block_f == 0 and d % block_d == 0
    nd = d // block_d
    grid = (e, c // block_c, f // block_f, nd)
    kernel = functools.partial(_gmm_kernel, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e_, i, j, k: (e_, i, k)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e_, i, j, k: (e_, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e_, i, j, k: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
