"""Mixture-of-Experts decoder (qwen3-moe 128e top-8, grok-1 8e top-2).

TPU-native dispatch: GShard/MaxText-style capacity-based routing with
one-hot dispatch/combine einsums, evaluated over token *chunks* (scanned)
so the dispatch tensor stays [chunk, E, C] — small enough for VMEM-friendly
lowering — while expert weights stay resident.  Expert parallelism comes
from GSPMD: expert-stacked weights [E, d, f] are sharded over the "model"
mesh axis on E (``moe_shard="expert"``, qwen3: 128/16 = 8 experts/device) or
on f (``moe_shard="ffn"``, grok: 8 experts don't divide a 16-way axis, so we
shard each expert's d_ff=32768 instead — Megatron-MoE TP).  The dispatch
einsums then lower to the all-to-all / all-gather collectives the roofline
analysis counts.

Tokens beyond an expert's capacity are dropped (standard GShard semantics,
capacity_factor 1.25); dropped tokens pass through the residual unchanged.
That is the training path.  Serving uses ``expert_layer``, which drops no
row whatever the imbalance.

The DeepSeek-V2 block is this family too: latent attention (MLA,
``kv_lora_rank > 0``), ``first_k_dense`` leading layers with a dense SwiGLU
(``params["dense_layers"]``), shared experts beside the routed ones, softmax
gates that are not renormalized (``norm_topk_prob=False``), and params that
hold ``experts_held`` experts from global id ``expert_offset`` (one chip's
share under expert parallelism) while the router spans all ``n_experts``.
A row's routed output is then the part its held experts give.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import blocks
from .api import ModelConfig

Array = jax.Array

CAPACITY_FACTOR = 1.25
MOE_CHUNK = 1024          # tokens routed per dispatch chunk


# ---------------------------------------------------------------------- init
def _init_attn(rng: Array, cfg: ModelConfig):
    if cfg.mla:
        return blocks.init_mla_params(rng, cfg, cfg.jdtype)
    return blocks.init_attn_params(rng, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.hd, cfg.jdtype,
                                   bias=cfg.qkv_bias)


def _init_layer(rng: Array, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(rng, 3)
    dt = cfg.jdtype
    E = cfg.held_experts
    ks = jax.random.split(k2, 3)
    lp = {
        "attn_norm": jnp.ones((cfg.d_model,), dt),
        "attn": _init_attn(k1, cfg),
        "ffn_norm": jnp.ones((cfg.d_model,), dt),
        "router": blocks.dense_init(k3, cfg.d_model, cfg.n_experts,
                                    jnp.float32),
        "experts": {
            "w_gate": jax.vmap(lambda k: blocks.dense_init(
                k, cfg.d_model, cfg.d_ff, dt))(jax.random.split(ks[0], E)),
            "w_up": jax.vmap(lambda k: blocks.dense_init(
                k, cfg.d_model, cfg.d_ff, dt))(jax.random.split(ks[1], E)),
            "w_down": jax.vmap(lambda k: blocks.dense_init(
                k, cfg.d_ff, cfg.d_model, dt))(jax.random.split(ks[2], E)),
        },
    }
    if cfg.d_ff_shared:
        lp["shared"] = blocks.init_swiglu_params(jax.random.fold_in(rng, 3),
                                                 cfg.d_model,
                                                 cfg.d_ff_shared, dt)
    return lp


def _init_dense_layer(rng: Array, cfg: ModelConfig):
    k1, k2 = jax.random.split(rng)
    dt = cfg.jdtype
    return {
        "attn_norm": jnp.ones((cfg.d_model,), dt),
        "attn": _init_attn(k1, cfg),
        "ffn_norm": jnp.ones((cfg.d_model,), dt),
        "ffn": blocks.init_swiglu_params(k2, cfg.d_model, cfg.d_ff_dense, dt),
    }


def init(rng: Array, cfg: ModelConfig) -> Dict:
    """``layers`` stacks the expert layers; ``dense_layers`` (only when
    ``first_k_dense``) the leading dense ones, which run first."""
    dt = cfg.jdtype
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers - cfg.first_k_dense)
    params = {
        "embed": blocks.embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dt),
        "layers": jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if cfg.first_k_dense:
        params["dense_layers"] = jax.vmap(lambda k: _init_dense_layer(k, cfg))(
            jax.random.split(jax.random.fold_in(rng, 3), cfg.first_k_dense))
    if not cfg.tie_embeddings:
        params["lm_head"] = blocks.dense_init(k_head, cfg.d_model,
                                              cfg.padded_vocab, dt)
    return params


# ------------------------------------------------------------------ routing
def _route_groups(x: Array, lp: Dict, cfg: ModelConfig,
                  capacity: int) -> Array:
    """Route grouped tokens through the experts — GShard dispatch.

    x: [G, c, d] -> y: [G, c, d].  Per group: one-hot dispatch D [c, E, C]
    and combine weights W [c, E, C]; tokens over a group's expert capacity
    are dropped.  All einsums carry the group dim g — no loop, so the HLO
    exposes the full dispatch FLOPs and EP collectives (all-to-all /
    all-gather over the expert-sharded weights) to the roofline analysis.
    """
    G, c, d = x.shape
    E, k = cfg.held_experts, cfg.top_k
    logits = jnp.einsum("gcd,de->gce", x.astype(jnp.float32),
                        lp["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                    # [g, c, n_experts]
    top_vals, top_idx = lax.top_k(gates, k)                    # [g, c, k]
    top_vals = _gate_weights(top_vals, cfg)

    # expert assignment mask per choice: [g, k, c, E]; a choice of an
    # expert these params do not hold is a row of zeros (no slot, no output)
    choice_mask = jax.nn.one_hot(
        jnp.moveaxis(top_idx, -1, 1) - cfg.expert_offset, E, dtype=jnp.int32)
    # position of each token in its expert queue (choice-major, GShard)
    flat_mask = choice_mask.reshape(G, k * c, E)
    pos_in_expert = jnp.cumsum(flat_mask, axis=1) - flat_mask  # [g, k*c, E]
    pos = jnp.sum(flat_mask * pos_in_expert, axis=-1).reshape(G, k, c)
    keep = (pos < capacity)                                    # [g, k, c]

    slot_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                             dtype=x.dtype)                    # [g, k, c, C]
    disp = jnp.einsum("gkce,gkcC->gceC", choice_mask.astype(x.dtype),
                      slot_oh)
    cdt = jnp.float32 if cfg.moe_comb_f32 else x.dtype
    comb = jnp.einsum("gkc,gkce,gkcC->gceC",
                      (jnp.moveaxis(top_vals, -1, 1) * keep).astype(cdt),
                      choice_mask.astype(cdt), slot_oh.astype(cdt))

    xe = jnp.einsum("gcd,gceC->geCd", x, disp)                 # [g, E, C, d]
    g_ = jnp.einsum("geCd,edf->geCf", xe, lp["experts"]["w_gate"])
    u = jnp.einsum("geCd,edf->geCf", xe, lp["experts"]["w_up"])
    h = jax.nn.silu(g_.astype(jnp.float32)).astype(x.dtype) * u
    if cfg.moe_fused_combine:
        # single contraction: the f-sharded ("ffn" EP-TP) partial sum is
        # reduced on the [g, c, d] result — E·C·capacity_factor×  smaller
        # than reducing the dispatched [g, E, C, d] intermediate
        y = jnp.einsum("geCf,efd,gceC->gcd", h,
                       lp["experts"]["w_down"],
                       comb.astype(x.dtype))
        return y.astype(x.dtype)
    ye = jnp.einsum("geCf,efd->geCd", h, lp["experts"]["w_down"])
    y = jnp.einsum("geCd,gceC->gcd", ye.astype(comb.dtype), comb)
    return y.astype(x.dtype)


def _gate_weights(top_vals: Array, cfg: ModelConfig) -> Array:
    """The top-k gates renormalized to sum 1 (``norm_topk_prob``), else
    times ``routed_scaling``."""
    if cfg.norm_topk_prob:
        return top_vals / jnp.maximum(
            jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    if cfg.routed_scaling != 1.0:
        return top_vals * cfg.routed_scaling
    return top_vals


def moe_ffn(x: Array, lp: Dict, cfg: ModelConfig) -> Array:
    """x: [B, S, d] -> [B, S, d], grouped GShard routing (group = 1024
    tokens; capacity per group = group·top_k·1.25/E)."""
    B, S, d = x.shape
    n_tok = B * S
    group = min(cfg.moe_group, n_tok)
    pad = (-n_tok) % group
    xf = x.reshape(n_tok, d)
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    G = xf.shape[0] // group
    capacity = max(1, int(math.ceil(group * cfg.top_k * CAPACITY_FACTOR
                                    / cfg.n_experts)))
    y = _route_groups(xf.reshape(G, group, d), lp, cfg, capacity)
    y = y.reshape(G * group, d)[:n_tok]
    return y.reshape(B, S, d)


# ------------------------------------------------------------ serving layer
SORTED_MIN_ROWS = 64      # from this many rows on, sorted rows + ragged_dot


def route(x: Array, lp: Dict, cfg: ModelConfig, valid: Array
          ) -> Tuple[Array, Array, Array]:
    """Top-k over all ``n_experts`` in float32: x [N, d] -> gate weights
    [N, k], each choice's index among the held experts [N, k], and whether
    the choice is held (and its row ``valid``) [N, k]."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         lp["router"].astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(gates, cfg.top_k)
        local = idx - cfg.expert_offset
        held = (local >= 0) & (local < cfg.held_experts) & valid[:, None]
        return _gate_weights(w, cfg), local, held


def _experts_dense(x: Array, w: Array, local: Array, held: Array,
                   ex: Dict) -> Array:
    """Every held expert on every row, weighted by the row's gates (zero
    where it did not choose the expert): few rows, as in a decode step."""
    E = ex["w_gate"].shape[0]
    comb = jnp.sum(jnp.where(held, w, 0.0)[..., None]
                   * jax.nn.one_hot(local, E, dtype=jnp.float32), axis=1)
    g = jnp.einsum("nd,edf->nef", x, ex["w_gate"])
    u = jnp.einsum("nd,edf->nef", x, ex["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("nef,efd->ned", h, ex["w_down"])
    return jnp.einsum("ne,ned->nd", comb, y.astype(jnp.float32))


def _experts_sorted(x: Array, w: Array, local: Array, held: Array,
                    ex: Dict) -> Array:
    """The (row, choice) pairs sorted by held expert, each expert's run of
    rows through its weights (``lax.ragged_dot``); pairs of experts not held
    sort last, outside every group, and give nothing."""
    N, k = local.shape
    E = ex["w_gate"].shape[0]
    key = jnp.where(held, local, E).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
    xs = x[order // k]
    f32 = jnp.float32
    g = lax.ragged_dot(xs, ex["w_gate"], sizes, preferred_element_type=f32)
    u = lax.ragged_dot(xs, ex["w_up"], sizes, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    ys = lax.ragged_dot(h, ex["w_down"], sizes, preferred_element_type=f32)
    grouped = jnp.arange(N * k) < jnp.sum(sizes)
    wp = jnp.where(held, w, 0.0).reshape(-1)[order]
    ys = jnp.where(grouped[:, None], ys * wp[:, None], 0.0)
    return ys[jnp.argsort(order)].reshape(N, k, -1).sum(axis=1)


def expert_layer(x: Array, lp: Dict, cfg: ModelConfig,
                 valid: Optional[Array] = None) -> Tuple[Array, Dict]:
    """Serving expert layer: x [N, d] -> (y [N, d], counts).

    y is what the held experts give, ``sum_{e in top-k, held} w_e E_e(x)``,
    plus the shared experts; no row is dropped whatever the imbalance.
    Rows not ``valid`` (pad rows, idle slots) are routed to no expert.
    Few rows (a decode step) run every held expert densely; more (a
    prefill chunk) go through ``_experts_sorted``.  ``counts``: the valid
    (row, choice) pairs that reached each held expert, ``expert_rows``
    [held], and all valid pairs, ``routed_pairs``."""
    N = x.shape[0]
    valid = jnp.ones((N,), bool) if valid is None else valid
    w, local, held = route(x, lp, cfg, valid)
    with jax.named_scope("moe.experts"):
        experts = (_experts_sorted if N >= SORTED_MIN_ROWS
                   else _experts_dense)
        y = experts(x, w, local, held, lp["experts"])
    if "shared" in lp:
        with jax.named_scope("moe.shared"):
            y = y + blocks.swiglu(x[None], lp["shared"])[0].astype(jnp.float32)
    counts = {
        "expert_rows": jnp.sum(jnp.where(held[..., None], jax.nn.one_hot(
            local, cfg.held_experts, dtype=jnp.int32), 0), axis=(0, 1)),
        "routed_pairs": jnp.sum(valid.astype(jnp.int32)) * cfg.top_k,
    }
    return y.astype(x.dtype), counts


# ------------------------------------------------------------------- forward
def _attend(lp: Dict, x: Array, positions: Array, cfg: ModelConfig) -> Array:
    """Causal self-attention over the whole sequence, either kind: GQA, or
    latent attention in its expanded form."""
    if cfg.mla:
        q_nope, q_pe, c_kv, k_pe = blocks.mla_project(x, lp["attn"],
                                                      positions, cfg)
        k, v = blocks.mla_expand(c_kv, k_pe, lp["attn"])
        q = jnp.concatenate([q_nope, q_pe], -1)
        scale = blocks.mla_softmax_scale(cfg)
    else:
        q, k, v = blocks.qkv_project(x, lp["attn"], cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd)
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
        scale = None
    o = blocks.attention(q, k, v, q_positions=positions, k_positions=positions,
                         causal=True, window=cfg.attn_window,
                         q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                         scale=scale)
    return blocks.out_project(o, lp["attn"])


def _layer_fwd(lp: Dict, h: Array, positions: Array, cfg: ModelConfig) -> Array:
    x = blocks.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    h = h + _attend(lp, x, positions, cfg)
    x = blocks.rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
    if "ffn" in lp:                               # a leading dense layer
        return h + blocks.swiglu(x, lp["ffn"])
    if "shared" in lp:
        h = h + blocks.swiglu(x, lp["shared"])
    h = h + moe_ffn(x, lp, cfg)
    return h


def forward(params: Dict, cfg: ModelConfig, tokens: Array, **_) -> Array:
    B, S = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    step = partial(_layer_fwd, positions=positions, cfg=cfg)
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat_policy == "dots" else None)
    body = (jax.checkpoint(lambda c, lp: (step(lp, c), None), policy=policy)
            if cfg.remat
            else (lambda c, lp: (step(lp, c), None)))
    if "dense_layers" in params:
        h, _ = lax.scan(body, h, params["dense_layers"])
    h, _ = lax.scan(body, h, params["layers"], unroll=cfg.scan_unroll)
    return _unembed(params, cfg, h)


def _unembed(params: Dict, cfg: ModelConfig, h: Array) -> Array:
    h = blocks.rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...d,dv->...v", h, table)


# -------------------------------------------------------------------- decode
def _gqa_only(cfg: ModelConfig) -> None:
    if cfg.mla or cfg.first_k_dense:
        raise NotImplementedError(
            "the DeepSeek-V2 block decodes through the paged engine "
            "(serve/model.py), not a dense cache")


def init_cache(cfg: ModelConfig, *, batch: int, max_len: int) -> Dict:
    _gqa_only(cfg)
    from . import transformer
    return transformer.init_cache(cfg, batch=batch, max_len=max_len)


def decode_step(params: Dict, cfg: ModelConfig, cache: Dict, token: Array,
                pos: Array) -> Tuple[Array, Dict]:
    _gqa_only(cfg)
    B = token.shape[0]
    C = cache["k"].shape[2]
    ring = cfg.attn_window is not None
    h = jnp.take(params["embed"], token[:, None], axis=0)
    positions = pos[:, None]
    slot = (pos % C) if ring else jnp.minimum(pos, C - 1)
    k_pos = cache["k_pos"].at[jnp.arange(B), slot].set(pos)
    capacity = max(1, int(math.ceil(B * cfg.top_k * CAPACITY_FACTOR
                                    / cfg.n_experts)))

    def body(h, xs):
        lp, ck, cv = xs
        x = blocks.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = blocks.qkv_project(x, lp["attn"], cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd)
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
        ck = ck.at[jnp.arange(B), slot].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[jnp.arange(B), slot].set(v[:, 0].astype(cv.dtype))
        o = blocks.attention(q, ck, cv, q_positions=positions,
                             k_positions=k_pos, causal=True,
                             window=cfg.attn_window, q_chunk=1,
                             kv_chunk=cfg.kv_chunk)
        h = h + blocks.out_project(o, lp["attn"])
        x = blocks.rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = h + _route_groups(x[:, 0][None], lp, cfg, capacity)[0][:, None]
        return h, (ck, cv)

    h, (new_k, new_v) = lax.scan(body, h, (params["layers"], cache["k"],
                                           cache["v"]),
                                 unroll=cfg.scan_unroll)
    logits = _unembed(params, cfg, h[:, 0])
    return logits, {"k": new_k, "v": new_v, "k_pos": k_pos}


def prefill(params: Dict, cfg: ModelConfig, tokens: Array, *, max_len: int,
            **_) -> Tuple[Array, Dict]:
    _gqa_only(cfg)
    B, S = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(h, lp):
        x = blocks.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = blocks.qkv_project(x, lp["attn"], cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd)
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
        o = blocks.attention(q, k, v, q_positions=positions,
                             k_positions=positions, causal=True,
                             window=cfg.attn_window, q_chunk=cfg.q_chunk,
                             kv_chunk=cfg.kv_chunk)
        h = h + blocks.out_project(o, lp["attn"])
        x = blocks.rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = h + moe_ffn(x, lp, cfg)
        return h, (k, v)

    h, (ks, vs) = lax.scan(body, h, params["layers"], unroll=cfg.scan_unroll)
    from . import transformer
    cache = transformer.init_cache(cfg, batch=B, max_len=max_len)
    C = cache["k"].shape[2]
    if S <= C:
        cache["k"] = lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
        cache["k_pos"] = lax.dynamic_update_slice(cache["k_pos"], positions,
                                                  (0, 0))
    else:
        last_pos = positions[:, S - C:]
        slots = last_pos % C
        b_idx = jnp.arange(B)[:, None]
        cache["k"] = cache["k"].at[:, b_idx, slots].set(
            ks[:, :, S - C:].astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[:, b_idx, slots].set(
            vs[:, :, S - C:].astype(cache["v"].dtype))
        cache["k_pos"] = cache["k_pos"].at[b_idx, slots].set(last_pos)
    logits = _unembed(params, cfg, h[:, -1])
    return logits, cache
