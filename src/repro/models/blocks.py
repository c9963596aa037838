"""Shared neural blocks (pure functions over param pytrees).

Everything here is written to be GSPMD-friendly: no data-dependent shapes,
fp32 accumulation in softmax/norms, memory-bounded attention (query/KV
chunked online-softmax scan) so the lowered HLO never materializes an
S x S score tensor — this is the pure-jnp oracle the Pallas flash kernels
are validated against, and the path XLA compiles inside the dry-run.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

# --------------------------------------------------------------------- init
def dense_init(rng: Array, d_in: int, d_out: int, dtype) -> Array:
    """Truncated-normal fan-in init (LLM standard)."""
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(rng, -3, 3, (d_in, d_out), jnp.float32)
            * std).astype(dtype)


def embed_init(rng: Array, vocab: int, d: int, dtype) -> Array:
    return (jax.random.truncated_normal(rng, -3, 3, (vocab, d), jnp.float32)
            * 0.02).astype(dtype)


# --------------------------------------------------------------------- norms
def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs     # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                           # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- YaRN RoPE
@dataclass(frozen=True)
class YaRN:
    """YaRN context extension of RoPE (arXiv:2309.00071), the
    ``rope_scaling`` of DeepSeek-V2's config.json."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_bound(rotations: float, dim: int, theta: float, max_pos: int) -> float:
    """Rotary pair index whose wavelength fits ``rotations`` turns into the
    original context."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_inv_freq(dim: int, theta: float, y: YaRN) -> Array:
    """Inverse frequencies of ``dim`` rotary dims: pairs below the low
    correction bound keep theta's, pairs above the high bound are divided by
    ``factor``, with a linear ramp between."""
    low = max(math.floor(_yarn_bound(y.beta_fast, dim, theta,
                                     y.original_max_position)), 0)
    high = min(math.ceil(_yarn_bound(y.beta_slow, dim, theta,
                                     y.original_max_position)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    extra = rope_freqs(dim, theta)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def apply_rope_interleaved(x: Array, positions: Array, inv_freq: Array,
                           mscale: float = 1.0) -> Array:
    """RoPE over rotary pairs ``(2i, 2i+1)`` (DeepSeek-V2's checkpoint
    layout), returned rotate-half style: evens' rotation, then odds'.
    x: [..., S, H, D]; positions broadcastable to [..., S]."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angles)[..., None, :] * mscale
    sin = jnp.sin(angles)[..., None, :] * mscale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- attention core
NEG_INF = -1e30


def _mask_value(q_pos: Array, k_pos: Array, causal: bool,
                window: Optional[int], kv_len: Optional[Array]) -> Array:
    """Additive mask [..., Sq, Sk] from absolute positions."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    # Empty cache slots carry position -2^30 and must never be attended;
    # every real position is >= 0.
    ok = kp >= 0
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    if kv_len is not None:                 # ragged decode: kv valid prefix
        ok &= kp < kv_len[..., None, None]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention(
    q: Array,                 # [B, Sq, H, D]
    k: Array,                 # [B, Sk, Hkv, D]
    v: Array,                 # [B, Sk, Hkv, D]
    *,
    q_positions: Array,       # [B, Sq] absolute positions
    k_positions: Array,       # [B, Sk]
    causal: bool = True,
    window: Optional[int] = None,
    kv_len: Optional[Array] = None,    # [B] valid KV prefix (decode)
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
    use_pallas: bool = False,
) -> Array:
    """Chunked online-softmax attention (GQA aware), fp32 accumulators.

    Peak live memory is O(B * q_chunk * H * kv_chunk) instead of O(B*Sq*H*Sk);
    the lowered HLO therefore fits the dry-run memory analysis at 32k/500k
    sequence lengths.  Semantics match ``kernels/flash_attention/ref.py``.
    """
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    if use_pallas and kv_len is None and Sq > 1:
        # contiguous-position training/prefill path → Pallas flash kernel
        # (interpret=True on CPU; compiled on TPU)
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal, window, scale)

    if Sq == 1:
        # single-token decode: full scores are only [B, H, Sk] — no chunk
        # loop.  GSPMD partitions softmax over a context-sharded cache with
        # tiny max/sum all-reduces (the decode-cell sharding baseline).
        qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, k.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        msk = _mask_value(q_positions, k_positions, causal, window, kv_len)
        s = s + msk[:, None, None, 0, :]
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
        o = o / jnp.maximum(l, 1e-30)
        return o.reshape(B, 1, H, Dv).astype(q.dtype)

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad to multiples
    pq = (-Sq) % q_chunk
    pk = (-Sk) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)),
                              constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        # padded kv positions = huge → masked out by causal/window/kv_len
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pk)),
                              constant_values=2**30)
    Sqp, Skp = Sq + pq, Sk + pk
    nq, nk = Sqp // q_chunk, Skp // kv_chunk

    qg = q.reshape(B, nq, q_chunk, Hkv, G, D)
    qpos = q_positions.reshape(B, nq, q_chunk)
    kg = k.reshape(B, nk, kv_chunk, Hkv, D)
    vg = v.reshape(B, nk, kv_chunk, Hkv, Dv)
    kpos = k_positions.reshape(B, nk, kv_chunk)

    def one_q_block(qb, qpb):
        # qb: [B, qc, Hkv, G, D]; qpb: [B, qc]
        def kv_step(carry, inp):
            acc, m, l = carry
            kb, vb, kpb = inp                       # [B, kc, Hkv, D], [B, kc]
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32),
                           preferred_element_type=jnp.float32) * scale
            msk = _mask_value(qpb, kpb, causal, window, kv_len)
            s = s + msk[:, :, None, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bqhgk,bkhd->bqhgd", p, vb.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, q_chunk, Hkv, G, Dv), jnp.float32)
        m0 = jnp.full((B, q_chunk, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, Hkv, G), jnp.float32)
        (acc, m, l), _ = lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.moveaxis(kg, 1, 0), jnp.moveaxis(vg, 1, 0),
             jnp.moveaxis(kpos, 1, 0)))
        return acc / jnp.maximum(l[..., None], 1e-30)

    if nq == 1:
        out = one_q_block(qg[:, 0], qpos[:, 0])[:, None]
    else:
        out = jax.vmap(one_q_block, in_axes=(1, 1), out_axes=1)(qg, qpos)
    out = out.reshape(B, Sqp, H, Dv)[:, :Sq]
    return out.astype(q.dtype)


# --------------------------------------------------------------- projections
def qkv_project(x: Array, p: dict, n_heads: int, n_kv_heads: int,
                head_dim: int) -> Tuple[Array, Array, Array]:
    """x: [B,S,Dm] -> q [B,S,H,D], k/v [B,S,Hkv,D].  Optional biases."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


# ------------------------------------------------------ latent attention (MLA)
# DeepSeek-V2 (arXiv:2405.04434), without query compression: q from x; a
# latent c_kv (normed, kv_lora_rank wide) and one roped k_pe shared by all
# heads from x; per head k_nope = c_kv W_uk and v = c_kv W_uv.  The cache
# holds (c_kv, k_pe).  ``cfg`` is the model's ``ModelConfig``.
def mla_rope(cfg) -> Tuple[Array, float]:
    """Inverse frequencies of the roped dims and the cos/sin scale."""
    d, y = cfg.qk_rope_head_dim, cfg.rope_scaling
    if y is None:
        return rope_freqs(d, cfg.rope_theta), 1.0
    return (yarn_inv_freq(d, cfg.rope_theta, y),
            yarn_mscale(y.factor, y.mscale)
            / yarn_mscale(y.factor, y.mscale_all_dim))


def mla_softmax_scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_mla_params(rng, cfg, dtype):
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 5)
    return {
        "wq": dense_init(ks[0], cfg.d_model, H * (dn + dr), dtype),
        "wkv_a": dense_init(ks[1], cfg.d_model, r + dr, dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "w_uk": dense_init(ks[2], r, H * dn, dtype).reshape(r, H, dn),
        "w_uv": dense_init(ks[3], r, H * dv, dtype).reshape(r, H, dv),
        "wo": dense_init(ks[4], H * dv, cfg.d_model, dtype),
    }


def mla_project(x: Array, p: dict, positions: Array, cfg
                ) -> Tuple[Array, Array, Array, Array]:
    """x [B,S,d] -> q_nope [B,S,H,dn], q_pe [B,S,H,dr] (roped), the normed
    latent c_kv [B,S,r] and the roped shared k_pe [B,S,dr]."""
    B, S, _ = x.shape
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, cfg.n_heads, -1)
    kv = jnp.einsum("bsd,dh->bsh", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    inv_freq, mscale = mla_rope(cfg)
    q_pe = apply_rope_interleaved(q[..., dn:], positions, inv_freq, mscale)
    k_pe = apply_rope_interleaved(kv[..., None, r:], positions, inv_freq,
                                  mscale)[..., 0, :]
    return q[..., :dn], q_pe, c_kv, k_pe


def mla_expand(c_kv: Array, k_pe: Array, p: dict) -> Tuple[Array, Array]:
    """Expanded form: latent [B,T,r] and k_pe [B,T,dr] -> per-head keys
    [B,T,H,dn+dr] and values [B,T,H,dv]."""
    k_nope = jnp.einsum("btr,rhn->bthn", c_kv, p["w_uk"])
    v = jnp.einsum("btr,rhv->bthv", c_kv, p["w_uv"])
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :],
                            k_nope.shape[:3] + k_pe.shape[-1:])
    return jnp.concatenate([k_nope, k_pe.astype(k_nope.dtype)], -1), v


def mla_absorbed_attention(q_nope: Array, q_pe: Array, c_kv: Array,
                           k_pe: Array, q_positions: Array,
                           k_positions: Array, p: dict, cfg) -> Array:
    """Absorbed form, one query a row: q_nope [B,H,dn], q_pe [B,H,dr] at
    q_positions [B] against the cached latent c_kv [B,T,r] and k_pe
    [B,T,dr] at k_positions [B,T] (causal, masked as ``attention`` masks).
    Scores (q_nope W_uk^T) . c_kv + q_pe . k_pe, output (p . c_kv) W_uv
    -> [B,H,dv].  No key or value is expanded."""
    with jax.named_scope("mla.absorb"):
        q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, p["w_uk"],
                           preferred_element_type=jnp.float32)
    with jax.named_scope("mla.attend"):
        s = (jnp.einsum("bhr,btr->bht", q_abs.astype(c_kv.dtype), c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhp,btp->bht", q_pe.astype(k_pe.dtype), k_pe,
                          preferred_element_type=jnp.float32))
        mask = _mask_value(q_positions[:, None], k_positions, True,
                           cfg.attn_window, None)                 # [B,1,T]
        s = s * mla_softmax_scale(cfg) + mask
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        ctx = jnp.einsum("bht,btr->bhr", e.astype(c_kv.dtype), c_kv,
                         preferred_element_type=jnp.float32)
        ctx = ctx / jnp.maximum(l, 1e-30)
    with jax.named_scope("mla.absorb"):
        return jnp.einsum("bhr,rhv->bhv", ctx.astype(q_nope.dtype), p["w_uv"])


def out_project(o: Array, p: dict) -> Array:
    B, S, H, D = o.shape
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * D), p["wo"])


def swiglu(x: Array, p: dict) -> Array:
    """SwiGLU FFN: (silu(x W_gate) * x W_up) W_down."""
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


def gelu_mlp(x: Array, p: dict) -> Array:
    """GELU MLP (whisper-style, with biases)."""
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"]) + p["b_up"].astype(x.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"]) + p["b_down"].astype(x.dtype)


def init_attn_params(rng, d_model, n_heads, n_kv_heads, head_dim, dtype,
                     bias=False):
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def init_swiglu_params(rng, d_model, d_ff, dtype):
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
        "w_up": dense_init(ks[1], d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d_model, dtype),
    }


def init_gelu_mlp_params(rng, d_model, d_ff, dtype):
    ks = jax.random.split(rng, 2)
    return {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


# ------------------------------------------------------------------ KV cache
def kv_cache_update(cache_k: Array, cache_v: Array, k_new: Array,
                    v_new: Array, pos: Array) -> Tuple[Array, Array]:
    """Scatter one decode step into the cache.

    cache_{k,v}: [B, S_max, Hkv, D]; k_new/v_new: [B, 1, Hkv, D];
    pos: [B] write positions (ragged batches supported).
    """
    B = cache_k.shape[0]
    b_idx = jnp.arange(B)
    cache_k = cache_k.at[b_idx, pos].set(k_new[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[b_idx, pos].set(v_new[:, 0].astype(cache_v.dtype))
    return cache_k, cache_v


def sliding_cache_update(cache_k: Array, cache_v: Array, k_new: Array,
                         v_new: Array, pos: Array) -> Tuple[Array, Array]:
    """Ring-buffer KV cache for sliding-window attention: slot = pos % W."""
    W = cache_k.shape[1]
    B = cache_k.shape[0]
    b_idx = jnp.arange(B)
    slot = pos % W
    cache_k = cache_k.at[b_idx, slot].set(k_new[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[b_idx, slot].set(v_new[:, 0].astype(cache_v.dtype))
    return cache_k, cache_v
