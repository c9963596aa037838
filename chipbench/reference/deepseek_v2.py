"""Plain float32 reference of a DeepSeek-V2 decoder, and the weights it uses.

Written from the architecture's description (DeepSeek-V2, arXiv:2405.04434,
as DeepSeek-V2-Lite's ``config.json`` sets it): token embedding; per layer
an RMS norm, multi-head latent attention, a second RMS norm and a
feed-forward, each with a residual; a final RMS norm and an untied output
head.  It imports nothing of the program.

Latent attention without query compression (``q_lora_rank`` null): per
head q = x W_q, split into a part without position (``qk_nope_head_dim``)
and a roped part (``qk_rope_head_dim``); a latent c = RMSNorm(x W_kv_a[:r])
of ``kv_lora_rank`` r and one roped key part k_pe = x W_kv_a[r:] shared by
all heads; per head k_nope and v from c W_kv_b.  Scores are
q_nope . k_nope + q_pe . k_pe over causal keys, scaled by
``(nope + rope)^-0.5 * mscale^2``.  RoPE is YaRN's (theta, ``factor``,
``original_max_position_embeddings``, ``beta_fast``/``beta_slow``), applied
to rotary pairs (2i, 2i+1), the checkpoint's layout.  This computes every
key and value in full (the expanded form).

The first ``first_k_dense_replace`` layers have a SiLU-gated MLP of
``intermediate_size``; the rest route each row by a softmax over all
``n_routed_experts_published`` experts, take the top ``num_experts_per_tok``
(``norm_topk_prob`` false: not renormalized; times
``routed_scaling_factor``), and add sum_e w_e E_e(x) over the chosen
experts this configuration holds (``n_routed_experts`` from
``first_held_expert``: one chip's share), plus ``n_shared_experts`` shared
experts, one SiLU-gated MLP of ``n_shared_experts * moe_intermediate_size``.

Every matrix product runs in float32 at ``Precision.HIGHEST``.  The
sequence, padded to one length so that one program serves every request,
goes through layer by layer: projections and the feed-forward for blocks
of rows, attention for blocks of query rows against the blocks of keys up
to theirs (the softmax rescaled as each key block comes in, which keeps it
exact); blocks of padding alone are skipped.  A 33k-token sequence fits
one chip beside nothing else.
``fp8=True`` is the control: every projection's weights and inputs, the
router's too, are rounded to float8 e4m3 with one scale per output column
and per row, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
E4M3_MAX = 448.0
ROW_BLOCK = 2048        # rows of the sequence padded to a bucket of these
BUCKETS = (32, 64)      # row blocks: one shape to compile; padding costs none
Q_BLOCK = 512           # query rows per attention block
K_BLOCK = 2048          # key rows per step of a query block
OUT_BLOCK = 256         # output rows padded to this multiple


def dims(config: Dict) -> Tuple:
    """Hashable sizes: (d, heads, nope, rope, v, r, dense ff, expert ff,
    shared ff, experts, held, first held, top-k, renormalize, scaling,
    eps, theta, yarn (factor, original, beta_fast, beta_slow, mscale,
    mscale_all_dim))."""
    y = config["rope_scaling"]
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"]),
            int(config["v_head_dim"]), int(config["kv_lora_rank"]),
            int(config["intermediate_size"]),
            int(config["moe_intermediate_size"]),
            int(config["n_shared_experts"]) * int(config["moe_intermediate_size"]),
            int(config["n_routed_experts_published"]),
            int(config["n_routed_experts"]), int(config["first_held_expert"]),
            int(config["num_experts_per_tok"]), bool(config["norm_topk_prob"]),
            float(config["routed_scaling_factor"]),
            float(config["rms_norm_eps"]), float(config["rope_theta"]),
            (float(y["factor"]), int(y["original_max_position_embeddings"]),
             float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
             float(y["mscale_all_dim"])))


# ------------------------------------------------------------------ weights
def init_weights(key, config: Dict, dtype=None) -> Dict:
    """Random weights from ``key`` (a raw ``uint32[2]`` key) in ``dtype``,
    by default the configuration's ``torch_dtype``, for the held experts
    only.  Matrices are normal with standard deviation
    1/sqrt(fan-in), the embedding 0.02; norm scales scatter around 1, so a
    fault in one shows.  Jit it: one call makes every leaf on the device."""
    dtype = dtype or jnp.dtype(config["torch_dtype"])
    d, h, nope, rope, v, r, ff, eff, sff, _, held = dims(config)[:11]
    E_all = int(config["n_routed_experts_published"])
    L = int(config["num_hidden_layers"])
    K = int(config["first_k_dense_replace"])
    vocab = int(config["vocab_size"])
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        # the hardware bit generator: at 3 billion values it compiles in a
        # fraction of the default generator's time
        k = jax.random.wrap_key_data(jnp.tile(next(ks), 2), impl="rbg")
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def attention(n):
        return {
            "input_norm": 1.0 + normal((n, d), 0.1),
            "q_proj": normal((n, d, h * (nope + rope)), d ** -0.5),
            "kv_a_proj": normal((n, d, r + rope), d ** -0.5),
            "kv_a_norm": 1.0 + normal((n, r), 0.1),
            "kv_b_proj": normal((n, r, h * (nope + v)), r ** -0.5),
            "o_proj": normal((n, h * v, d), (h * v) ** -0.5),
            "post_attention_norm": 1.0 + normal((n, d), 0.1),
        }

    def mlp(lead, width):
        return {"gate_proj": normal(lead + (d, width), d ** -0.5),
                "up_proj": normal(lead + (d, width), d ** -0.5),
                "down_proj": normal(lead + (width, d), width ** -0.5)}

    n = L - K
    w = {"embed": normal((vocab, d), 0.02),
         "dense": dict(attention(K), **mlp((K,), ff)),
         "moe": dict(attention(n), gate=normal((n, d, E_all), d ** -0.5),
                     experts=mlp((n, held), eff), shared=mlp((n,), sff)),
         "final_norm": 1.0 + normal((d,), 0.1),
         "lm_head": normal((d, vocab), d ** -0.5)}
    return w


# ------------------------------------------------------------------ pieces
def _e4m3(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _e4m3(x, -1), _e4m3(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope_tables(pos, rope: int, theta: float, yarn: Tuple):
    """YaRN's cos and sin [T, rope/2] at positions ``pos``."""
    factor, orig, beta_fast, beta_slow, m, m_all = yarn

    def bound(rotations):
        return rope * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(bound(beta_fast)), 0)
    high = min(math.ceil(bound(beta_slow)), rope - 1)
    base = 1.0 / theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    # 0 below the low bound (theta's frequency), 1 above the high bound
    # (divided by factor), linear between
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv = base * (1.0 - ramp) + base / factor * ramp
    ang = pos.astype(jnp.float32)[:, None] * inv
    scale = _yarn_mscale(factor, m) / _yarn_mscale(factor, m_all)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, cos, sin):
    """x [T, ..., rope] with rotary pairs (2i, 2i+1) -> the rotated pairs,
    evens' results first, then odds'."""
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(x, w, fp8):
    return _mm(jax.nn.silu(_mm(x, w["gate_proj"], fp8)) * _mm(x, w["up_proj"], fp8),
               w["down_proj"], fp8)


def _mm_experts(x, w, fp8: bool):
    """x [R, din] (or [E, R, din]) through each expert's w [E, din, dout]
    -> [E, R, dout]."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _e4m3(x, -1), _e4m3(w, 1)
    spec = "rd,edf->erf" if x.ndim == 2 else "erd,edf->erf"
    return jnp.einsum(spec, x, w, precision=HI)


def _routed(x, lw, dm, fp8):
    """The held experts' part of the routed output of rows x [R, d]."""
    (_, _, _, _, _, _, _, _, _, E_all, held, first, k, renorm, scaling) = dm[:15]
    gates = jax.nn.softmax(_mm(x, lw["gate"], fp8), axis=-1)     # [R, E_all]
    top, idx = lax.top_k(gates, k)
    top = (top / jnp.sum(top, -1, keepdims=True)) if renorm else top * scaling
    # each held expert's gate on each row: its top-k weight, 0 if not chosen
    mine = idx[:, :, None] == first + jnp.arange(held)           # [R, k, held]
    weight = jnp.sum(jnp.where(mine, top[:, :, None], 0.0), axis=1)
    ex = lw["experts"]
    hidden = (jax.nn.silu(_mm_experts(x, ex["gate_proj"], fp8))
              * _mm_experts(x, ex["up_proj"], fp8))
    out = _mm_experts(hidden, ex["down_proj"], fp8)               # [held, R, d]
    return jnp.einsum("re,erd->rd", weight, out, precision=HI)


def _blocks(fn, xs, n_valid):
    """``fn(i, *row blocks)`` over the ROW_BLOCK-row blocks of the arrays
    ``xs`` [T, ...]; a block wholly past ``n_valid`` (padding) is not
    computed and gives zeros."""
    T = xs[0].shape[0]
    blocks = [x.reshape((T // ROW_BLOCK, ROW_BLOCK) + x.shape[1:]) for x in xs]

    def one(args):
        i, *b = args
        return lax.cond(i * ROW_BLOCK < n_valid, fn,
                        lambda *a: jax.tree_util.tree_map(jnp.zeros_like, fn(*a)),
                        i, *b)

    out = lax.map(one, (jnp.arange(T // ROW_BLOCK), *blocks))
    return jax.tree_util.tree_map(lambda a: a.reshape((T,) + a.shape[2:]), out)


@partial(jax.jit, static_argnames=("dm", "fp8", "moe"))
def _layer(h, lw, n_valid, *, dm: Tuple, fp8: bool, moe: bool):
    """One decoder layer over the whole (padded) sequence ``h`` [T, d]."""
    d, nh, nope, rope, vd, r = dm[:6]
    eps, theta, yarn = dm[15:18]
    T = h.shape[0]
    pos = jnp.arange(T)

    def project(i, hb):
        cos, sin = _rope_tables(i * ROW_BLOCK + jnp.arange(ROW_BLOCK), rope,
                                theta, yarn)
        x = _rms(hb, lw["input_norm"], eps)
        q = _mm(x, lw["q_proj"], fp8).reshape(ROW_BLOCK, nh, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
        kv_a = _mm(x, lw["kv_a_proj"], fp8)
        c = _rms(kv_a[:, :r], lw["kv_a_norm"], eps)
        k_pe = _rope(kv_a[:, r:], cos, sin)                        # [B, rope]
        kv = _mm(c, lw["kv_b_proj"], fp8).reshape(ROW_BLOCK, nh, nope + vd)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe[:, None], (ROW_BLOCK, nh, rope))], -1)
        return q, k, kv[..., nope:]

    q, k, v = _blocks(project, (h,), n_valid)
    factor, m_all = yarn[0], yarn[5]
    scale = (nope + rope) ** -0.5 * _yarn_mscale(factor, m_all) ** 2
    key_ok = pos < n_valid

    def attend(i):
        """Query block i against the key blocks up to it, softmax kept
        exact by rescaling as each key block comes in."""
        qb = lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)

        def key_block(j, carry):
            acc, m, l = carry
            kb = lax.dynamic_slice_in_dim(k, j * K_BLOCK, K_BLOCK, 0)
            vb = lax.dynamic_slice_in_dim(v, j * K_BLOCK, K_BLOCK, 0)
            kpos = j * K_BLOCK + jnp.arange(K_BLOCK)
            ok = (kpos[None, :] <= qpos[:, None]) & \
                lax.dynamic_slice_in_dim(key_ok, j * K_BLOCK, K_BLOCK)[None, :]
            s = jnp.einsum("bhd,khd->hbk", qb, kb, precision=HI) * scale
            s = jnp.where(ok, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, -1))
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum("hbk,khd->hbd", p, vb,
                                                     precision=HI)
            return acc, m_new, l * corr + jnp.sum(p, -1)

        init = (jnp.zeros((nh, Q_BLOCK, vd)), jnp.full((nh, Q_BLOCK), -1e30),
                jnp.zeros((nh, Q_BLOCK)))
        # the key blocks up to the block's last row and the sequence's end;
        # none for a block of padding
        end = jnp.minimum((i + 1) * Q_BLOCK, n_valid)
        last = jnp.where(i * Q_BLOCK < n_valid, (end + K_BLOCK - 1) // K_BLOCK, 0)
        acc, _, l = lax.fori_loop(0, last, key_block, init)
        o = acc / jnp.maximum(l, 1e-30)[..., None]                 # [h, b, v]
        return jnp.moveaxis(o, 0, 1).reshape(Q_BLOCK, nh * vd)

    o = lax.map(attend, jnp.arange(T // Q_BLOCK)).reshape(T, nh * vd)

    def out_and_ffn(i, hb, ob):
        hb = hb + _mm(ob, lw["o_proj"], fp8)
        xb = _rms(hb, lw["post_attention_norm"], eps)
        if not moe:
            return hb + _mlp(xb, lw, fp8)
        return hb + _mlp(xb, lw["shared"], fp8) + _routed(xb, lw, dm, fp8)

    return _blocks(out_and_ffn, (h, o), n_valid)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(h, rows, w_norm, w_head, *, eps: float, fp8: bool):
    return _mm(_rms(h[rows], w_norm, eps), w_head, fp8)


# ------------------------------------------------------------------ forward
def logits_at(w: Dict, config: Dict, tokens, rows, *, fp8: bool = False):
    """Logits [len(rows), vocab], float32, of the positions ``rows`` of the
    sequence ``tokens`` (position r predicts token r + 1)."""
    dm = dims(config)
    n = len(tokens)
    T = ROW_BLOCK * next(b for b in BUCKETS if b * ROW_BLOCK >= n)
    ids = jnp.zeros((T,), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    h = jnp.take(w["embed"], ids, axis=0).astype(jnp.float32)
    n_valid = jnp.int32(n)
    for group, moe in (("dense", False), ("moe", True)):
        for layer in range(w[group]["input_norm"].shape[0]):
            lw = jax.tree_util.tree_map(lambda a: a[layer], w[group])
            h = _layer(h, lw, n_valid, dm=dm, fp8=fp8, moe=moe)
    R = len(rows)
    Rp = -(-R // OUT_BLOCK) * OUT_BLOCK
    r = jnp.zeros((Rp,), jnp.int32).at[:R].set(jnp.asarray(rows, jnp.int32))
    return _head(h, r, w["final_norm"], w["lm_head"], eps=dm[15],
                 fp8=fp8)[:R]
