"""jit'd public wrappers around the Pallas kernels.

`interpret` is a required keyword: True runs the kernel body in the
Pallas interpreter (any backend), False lowers it through Mosaic for
the TPU. No wrapper picks one from the attached device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import mlstm as _ml
from repro.kernels import moe_gmm as _gmm
from repro.kernels import rglru_scan as _rg


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    interpret):
    """(B, H, S, K) attention via the tiled online-softmax kernel."""
    b, h, s, kd = q.shape
    fold = lambda t: t.reshape(b * h, t.shape[2], t.shape[3])
    out = _fa.flash_attention(fold(q), fold(k), fold(v), causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out.reshape(b, h, s, kd)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "block_d", "interpret"))
def grouped_matmul(x, w, *, block_c=128, block_f=128, block_d=256,
                   interpret):
    return _gmm.grouped_matmul(x, w, block_c=block_c, block_f=block_f,
                               block_d=block_d, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "block_d",
                                             "interpret"))
def rglru_scan(a, x, *, block_s=256, block_d=512, interpret):
    return _rg.rglru_scan(a, x, block_s=block_s, block_d=block_d,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise(q, k, v, log_i, log_f, *, chunk=64, interpret):
    return _ml.mlstm_chunkwise(q, k, v, log_i, log_f, chunk=chunk,
                               interpret=interpret)
