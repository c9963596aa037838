"""Pallas TPU flash-attention forward kernel (causal / SWA / GQA).

Tiling: grid = (B, H, nQ, nK); per grid step one (block_q × block_k) score
tile lives in VMEM, with fp32 running (acc, m, l) accumulators (m and l as
[block_q, 1] columns: Mosaic refuses 1-D row statistics) carried in
VMEM scratch across the sequential nK dimension (TPU grids iterate the
minor-most axis innermost, so scratch carries are the canonical flash
pattern).  Block sizes default to 128×128 — MXU-aligned (the MXU consumes
128×128 tiles; the head dim is padded to a multiple of 128 by ops.py).

GQA is handled in the index_map: query head h reads KV head h // group.
Causality/SWA skip fully-masked tiles via ``pl.when`` (the tile still
occupies a grid step but does no FLOPs on TPU).
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: Optional[int],
                block_q: int, block_k: int, n_k: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # tile-level skip: fully above the diagonal / outside the window / past
    # the valid kv prefix
    relevant = True
    if causal:
        relevant = k_start <= q_start + block_q - 1
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window)

    @pl.when(jnp.logical_and(relevant, k_start < kv_len))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        ok = kpos < kv_len
        if causal:
            ok = jnp.logical_and(ok, kpos <= qpos)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                                  # [bq, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # rows where everything is masked: exp(NEG-NEG)=1 ⇒ zero them
        p = jnp.where(m_new == NEG_INF, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,                # [B, H, Sq, D]   (D multiple of 128)
    k: jax.Array,                # [B, Hkv, Sk, D]
    v: jax.Array,                # [B, Hkv, Sk, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    kv_len: Optional[int] = None,     # valid KV prefix (≤ Sk)
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    kv_len = Sk if kv_len is None else kv_len
    n_q = Sq // block_q
    n_k = Sk // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
        ],
        interpret=interpret,
    )(q, k, v)
