"""Pallas TPU chunked mLSTM scan kernel.

The xlstm/hymba analogue of flash attention: within a T_c-length chunk the
stabilized recurrence is evaluated as decay-masked [T_c × T_c] matmuls on
the MXU; the (C, n, m) matrix-memory state carries across chunks in VMEM
scratch (grid iterates chunks sequentially per (batch·head) row).

grid = (BH, n_chunks);  blocks: q/k/v (1, T_c, D), gates (1, T_c, 1) —
columns, because a (1, T_c) row block breaks the TPU's block-shape rule
for T_c < 128; the row copies the decay matrix needs are taken in-kernel
with masked sublane reductions.  scratch: C [D, D] f32, n [1, D] f32, m [1, 1] f32.
D = head dim (xlstm-1.3b: 512 → a 512×512 f32 state = 1 MB VMEM, fits
comfortably).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref, h_ref,
                  C_ref, n_ref, m_ref, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    T = chunk
    D = q_ref.shape[-1]
    q = q_ref[0].astype(jnp.float32) * (1.0 / math.sqrt(D))   # [T, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lf_c = jax.nn.log_sigmoid(fg_ref[0].astype(jnp.float32))  # [T, 1]
    g_c = ig_ref[0].astype(jnp.float32)                       # [T, 1]

    row = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    causal = col <= row

    def to_row(x_c):                                          # [T,1]→[1,T]
        return jnp.sum(jnp.where(row == col, x_c, 0.0), axis=0,
                       keepdims=True)

    lf_r = to_row(lf_c)
    g_r = to_row(g_c)
    # inclusive prefix sums of the log forget gates, in both orientations
    b_c = jnp.sum(jnp.where(causal, lf_r, 0.0), axis=1, keepdims=True)
    b_r = jnp.sum(jnp.where(row <= col, lf_c, 0.0), axis=0, keepdims=True)
    b_last = jnp.sum(lf_r, axis=1, keepdims=True)             # [1, 1]

    dmat = jnp.where(causal, b_c - b_r + g_r, NEG)

    m_prev = m_ref[...]                                       # [1, 1]
    C_s = C_ref[...]
    n_s = n_ref[...]                                          # [1, D]

    alpha = m_prev + b_c                                      # [T, 1]
    m_t = jnp.maximum(alpha, jnp.max(dmat, axis=1, keepdims=True))
    wmat = jnp.exp(dmat - m_t)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * wmat
    inter = jnp.exp(alpha - m_t)
    h_num = (jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
             + inter * jax.lax.dot(q, C_s,
                                   preferred_element_type=jnp.float32))
    n_t = (jax.lax.dot(wmat, k, preferred_element_type=jnp.float32)
           + inter * n_s)
    qn = jnp.abs(jnp.sum(q * n_t, axis=-1, keepdims=True))
    denom = jnp.maximum(qn, jnp.exp(-m_t))
    h_ref[0] = (h_num / denom).astype(h_ref.dtype)

    # carry update
    m_new = jnp.maximum(m_prev + b_last,
                        jnp.max(b_last - b_r + g_r, axis=1, keepdims=True))
    sc = jnp.exp(m_prev + b_last - m_new)
    kw = k * jnp.exp(b_last - b_c + g_c - m_new)              # [T, D]
    C_ref[...] = sc * C_s + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_ref[...] = sc * n_s + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[...] = m_new


def mlstm_scan_kernel(q, k, v, ig, fg, *, chunk: int = 64,
                      interpret: bool = False):
    """q/k/v: [BH, S, D]; ig/fg: [BH, S]; S must be a chunk multiple."""
    BH, S, D = q.shape
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, ig[..., None], fg[..., None])
