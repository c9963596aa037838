"""Pallas TPU flash-decode kernel.

One query token per sequence attends over a blocked KV cache — the rollout
stage's HBM-bound hot loop (the paper's Observation 1: decode reads the
whole cache + weights per token, so HBM bandwidth is the roof).

Tiling: grid = (B, nC).  Per step, one (block_c × Hkv × D) KV tile
streams HBM→VMEM with all of its heads (the block's last two dims are the
cache's own ``(Hkv, D)``, as the TPU's block-shape rule asks); for each KV
head the G query heads of its group score against it on the MXU; fp32
(acc, m, l) accumulators, 2-D per head, live in VMEM scratch across the
sequential cache dimension.  The query positions ride in as a scalar-
prefetch operand.  Ragged batches are handled by per-slot absolute
positions (k_pos; empty slots carry −2^30) — the same convention as the
ring-buffer caches in models/.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, window: Optional[int], n_c: int, n_kv: int):
    b = pl.program_id(0)
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = qpos_ref[b]                               # scalar (prefetch)
    kpos = kpos_ref[0]                               # [1, bc]
    ok = jnp.logical_and(kpos >= 0, kpos <= qpos)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)

    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32) * scale          # [G, D]
        k = k_ref[0, :, h, :].astype(jnp.float32)            # [bc, D]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G, bc]
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[h]                                     # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new == NEG_INF, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = (acc_ref[h] * corr
                      + jax.lax.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32))
        m_ref[h] = m_new

    @pl.when(ic == n_c - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,          # [B, Hkv, G, D]
    k: jax.Array,          # [B, C, Hkv, D]
    v: jax.Array,          # [B, C, Hkv, D]
    q_pos: jax.Array,      # [B] int32
    k_pos: jax.Array,      # [B, C] int32
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, D = q.shape
    _, C, _, _ = k.shape
    assert C % block_c == 0, (C, block_c)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    n_c = C // block_c

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               n_c=n_c, n_kv=Hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # q_pos
        grid=(B, n_c),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, ic, qp: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_c, Hkv, D),
                         lambda b, ic, qp: (b, ic, 0, 0)),
            pl.BlockSpec((1, block_c, Hkv, D),
                         lambda b, ic, qp: (b, ic, 0, 0)),
            pl.BlockSpec((1, 1, block_c),
                         lambda b, ic, qp: (b, 0, ic)),      # k_pos
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, ic, qp: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(q_pos, q, k, v, k_pos.reshape(B, 1, C))
