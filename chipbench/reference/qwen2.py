"""Plain float32 reference of a Qwen2-type decoder, and the weights it uses.

Written from the architecture's description (Qwen2, as in DeepSeek-R1-
Distill-Qwen's ``config.json``): token embedding; per layer an RMS norm,
attention with biased q/k/v projections, rotary position embedding
(rotate-half, ``rope_theta``), grouped key/value heads and causal softmax,
an output projection, a second RMS norm and a SiLU-gated MLP, each with a
residual; a final RMS norm and an untied output head.  It imports nothing
of the program.

Every matrix product runs in float32 at ``Precision.HIGHEST``.  The
sequence goes through layer by layer; attention is computed for blocks of
query rows against the keys up to their block, and the MLP for blocks of
rows, so a 33k-token sequence fits one chip beside nothing else.
``fp8=True`` is the control: every projection's weights and inputs are
rounded to float8 e4m3 with one scale per output column and per row, the
step below the configuration's bfloat16.
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
BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64)    # few shapes to compile
Q_BLOCK = 256           # query rows per attention block
OUT_BLOCK = 256         # output rows padded to this multiple


def dims(config: Dict) -> Tuple:
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return (d, int(config["intermediate_size"]), int(config["num_hidden_layers"]),
            h, int(config["num_key_value_heads"]),
            int(config.get("head_dim") or d // h), int(config["vocab_size"]),
            float(config["rms_norm_eps"]), float(config["rope_theta"]))


# ------------------------------------------------------------------ weights
def init_weights(key, config: Dict, dtype=jnp.bfloat16) -> Dict:
    """Random weights from ``key`` in the configuration's dtype.  Matrices
    are normal with standard deviation 1/sqrt(fan-in), the embedding 0.02;
    norm scales scatter around 1 and biases around 0, so that a fault in
    either shows.  Jit it: one call makes every leaf on the device."""
    d, ff, L, h, kv, hd, vocab, _, _ = dims(config)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dtype)

    layers = {
        "input_norm": 1.0 + normal((L, d), 0.1),
        "q_proj": normal((L, d, h * hd), d ** -0.5),
        "k_proj": normal((L, d, kv * hd), d ** -0.5),
        "v_proj": normal((L, d, kv * hd), d ** -0.5),
        "o_proj": normal((L, h * hd, d), (h * hd) ** -0.5),
        "q_bias": normal((L, h * hd), 0.1),
        "k_bias": normal((L, kv * hd), 0.1),
        "v_bias": normal((L, kv * hd), 0.1),
        "post_attention_norm": 1.0 + normal((L, d), 0.1),
        "gate_proj": normal((L, d, ff), d ** -0.5),
        "up_proj": normal((L, d, ff), d ** -0.5),
        "down_proj": normal((L, ff, d), ff ** -0.5),
    }
    w = {"embed": normal((vocab, d), 0.02), "layers": layers,
         "final_norm": 1.0 + normal((d,), 0.1)}
    if not config["tie_word_embeddings"]:
        w["lm_head"] = normal((d, vocab), d ** -0.5)
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


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv                 # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _layer(h, lw, n_valid, *, dm: Tuple, fp8: bool):
    """One decoder layer over the whole (padded) sequence ``h`` [T, d]."""
    d, ff, _, nh, nkv, hd, _, eps, theta = dm
    T = h.shape[0]
    pos = jnp.arange(T)
    x = _rms(h, lw["input_norm"], eps)
    q = _mm(x, lw["q_proj"], fp8) + lw["q_bias"].astype(jnp.float32)
    k = _mm(x, lw["k_proj"], fp8) + lw["k_bias"].astype(jnp.float32)
    v = _mm(x, lw["v_proj"], fp8) + lw["v_bias"].astype(jnp.float32)
    q = _rope(q.reshape(T, nh, hd), pos, theta).reshape(T, nkv, nh // nkv, hd)
    k = _rope(k.reshape(T, nkv, hd), pos, theta)
    v = v.reshape(T, nkv, hd)
    key_ok = pos < n_valid

    def attend(q_rows, keys):
        def block(i):
            qb = lax.dynamic_slice_in_dim(q_rows[0], i * Q_BLOCK, Q_BLOCK, 0)
            qpos = q_rows[1] + i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.einsum("bhgd,khd->hgbk", qb, k[:keys],
                           precision=HI) / math.sqrt(hd)
            ok = (pos[None, :keys] <= qpos[:, None]) & key_ok[None, :keys]
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            return jnp.einsum("hgbk,khd->bhgd", p, v[:keys],
                              precision=HI).reshape(Q_BLOCK, nh * hd)
        n = q_rows[0].shape[0] // Q_BLOCK
        return lax.map(block, jnp.arange(n)).reshape(-1, nh * hd)

    # causal: the rows of each ROW_BLOCK attend only to the keys up to it
    o = jnp.concatenate([
        attend((q[j:j + ROW_BLOCK], j), j + ROW_BLOCK)
        for j in range(0, T, ROW_BLOCK)])
    h = h + _mm(o, lw["o_proj"], fp8)

    def mlp(hb):
        xb = _rms(hb, lw["post_attention_norm"], eps)
        g = _mm(xb, lw["gate_proj"], fp8)
        u = _mm(xb, lw["up_proj"], fp8)
        return hb + _mm(jax.nn.silu(g) * u, lw["down_proj"], fp8)

    return lax.map(mlp, h.reshape(T // ROW_BLOCK, ROW_BLOCK, d)).reshape(T, d)


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _head(h, rows, w_norm, w_head, *, dm: Tuple, fp8: bool):
    eps = dm[7]
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
    for layer in range(dm[2]):
        lw = jax.tree_util.tree_map(lambda a: a[layer], w["layers"])
        h = _layer(h, lw, n_valid, dm=dm, fp8=fp8)
    R = len(rows)
    Rp = -(-R // OUT_BLOCK) * OUT_BLOCK
    r = jnp.zeros((Rp,), jnp.int32).at[:R].set(jnp.asarray(rows, jnp.int32))
    head = w["embed"].T if config["tie_word_embeddings"] else w["lm_head"]
    return _head(h, r, w["final_norm"], head, dm=dm, fp8=fp8)[:R]
