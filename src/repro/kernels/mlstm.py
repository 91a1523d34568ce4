"""Pallas TPU chunkwise mLSTM (matrix-memory xLSTM cell).

Grid: (batch*heads, chunks sequential). The (C, n, m) recurrent state
carries across chunks in VMEM scratch; within a chunk the stabilized
parallel form runs on the MXU (two block matmuls + decay matrix).
Mirrors models/xlstm.mlstm_chunkwise (the oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, h_ref,
                  c_ref, n_ref, m_ref, *, chunk: int, k_dim: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    scale = k_dim ** -0.5
    q = q_ref[0].astype(jnp.float32) * scale          # (L, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32)             # (L,)
    lf = lf_ref[0, 0].astype(jnp.float32)

    C = c_ref[...]
    n = n_ref[...]                                    # (1, K)
    m = m_ref[0, 0]

    F = jnp.cumsum(lf)                                # (L,)
    W = F[:, None] - F[None, :] + li[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    W = jnp.where(tri, W, NEG)
    g_inter = m + F                                   # (L,)
    m_loc = jnp.maximum(g_inter, W.max(-1))
    D = jnp.exp(W - m_loc[:, None])
    c_int = jnp.exp(g_inter - m_loc)
    qk = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    num = c_int[:, None] * jnp.dot(q, C,
                                   preferred_element_type=jnp.float32) \
        + jnp.dot(D * qk, v, preferred_element_type=jnp.float32)
    den = c_int * jnp.dot(q, n.T,
                          preferred_element_type=jnp.float32)[:, 0] \
        + jnp.sum(D * qk, -1)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_loc))[:, None]
    h_ref[0] = h.astype(h_ref.dtype)

    # carry to chunk end
    Ftot = F[-1]
    scale_s = li + Ftot - F
    m_new = jnp.maximum(m + Ftot, scale_s.max())
    w_s = jnp.exp(scale_s - m_new)
    c_ref[...] = jnp.exp(m + Ftot - m_new) * C + jnp.dot(
        (w_s[:, None] * k).T, v, preferred_element_type=jnp.float32)
    n_ref[...] = jnp.exp(m + Ftot - m_new) * n + \
        jnp.sum(w_s[:, None] * k, 0, keepdims=True)
    m_ref[0, 0] = m_new


def mlstm_chunkwise(q, k, v, log_i, log_f, *, chunk: int = 64,
                    interpret: bool = True):
    """q,k,v: (BH, S, K); log_i/log_f: (BH, S). Returns h (BH, S, K)."""
    bh, s, kd = q.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    grid = (bh, s // chunk)
    gates_spec = pl.BlockSpec((1, 1, chunk),
                              lambda b, c: (b, 0, c))
    kernel = functools.partial(_mlstm_kernel, chunk=chunk, k_dim=kd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, kd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kd), lambda b, c: (b, c, 0)),
            gates_spec, gates_spec,
        ],
        out_specs=pl.BlockSpec((1, chunk, kd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, kd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((kd, kd), jnp.float32),     # C
            pltpu.VMEM((1, kd), jnp.float32),      # n
            pltpu.VMEM((1, 1), jnp.float32),       # m
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_i.reshape(bh, 1, s), log_f.reshape(bh, 1, s))
