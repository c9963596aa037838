"""Pallas TPU paged decode-attention kernel.

The continuous-batching engine keeps the KV cache in fixed-size pages
scattered through a global pool; a sequence's context is the *non-
contiguous* set of pages named by its block table.  Per decode token the
kernel streams exactly the sequence's own pages HBM→VMEM — the serving
hot loop stays HBM-bound on useful bytes (paper Observation 1) instead of
on a right-padded dense cache.

Tiling: grid = (B, maxp).  Block tables and lengths ride in as
scalar-prefetch operands so the KV BlockSpec index maps *gather*: step
(b, ip) DMAs physical page ``block_tables[b, ip]`` with all of its Hkv
heads in one block, whose last two dims ``(Hkv, D)`` are the pool's own,
as the TPU's block-shape rule asks; the kernel loops over the heads.  fp32
(acc, m, l) accumulators, 2-D per head, live in VMEM scratch across the
sequential page axis; pages wholly past the sequence length are skipped
with ``pl.when`` (their DMA still lands, so unused table entries must
point at a valid page — the pool reserves page 0 as that null sink).  The
tail page is masked by logical slot position, mirroring the ragged-batch
convention of ``kernels/decode_attention``.
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


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, window: Optional[int], page: int, maxp: int,
                  n_kv: int):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]                              # scalar (prefetch)
    start = ip * page                                # logical slot of row 0

    @pl.when(start < length)
    def _compute():
        slot = start + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        ok = slot < length
        if window is not None:
            ok = jnp.logical_and(ok, slot > (length - 1) - window)
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32) * scale       # [G, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)         # [page, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(ok, s, NEG_INF)            # ok: [1, page] broadcasts

            m_prev = m_ref[h]                                  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(m_new == NEG_INF, 0.0, p)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = (acc_ref[h] * corr
                          + jax.lax.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32))
            m_ref[h] = m_new

    @pl.when(ip == maxp - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_flash_decode(
    q: jax.Array,              # [B, Hkv, G, D]
    k_pages: jax.Array,        # [P, page, Hkv, D]
    v_pages: jax.Array,        # [P, page, Hkv, D]
    block_tables: jax.Array,   # [B, maxp] int32
    lengths: jax.Array,        # [B] int32
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, D = q.shape
    _, page, _, _ = k_pages.shape
    maxp = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               page=page, maxp=maxp, n_kv=Hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # block_tables, lengths
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, ip, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, ip, bt, ln: (bt[b, ip], 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, ip, bt, ln: (bt[b, ip], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D),
                               lambda b, ip, bt, ln: (b, 0, 0, 0)),
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
    )(block_tables, lengths, q, k_pages, v_pages)
