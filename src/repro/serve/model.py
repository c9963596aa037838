"""Paged forward passes for the dense-transformer and expert families.

Mirrors ``models/transformer.py``'s prefill/decode math exactly (same
blocks, same rope, same masked-softmax attention semantics) but reads and
writes the **paged** cache: per step, new K/V land at logical slot
``pos`` → physical ``(table[pos // page], pos % page)``, and decode
attention runs either through the Pallas ``paged_attention`` kernel
(``cfg.use_pallas``) or a split-K loop over the live pages (below), each
masking every pool slot past the context exactly — so a paged greedy
decode is token-identical to the dense engine's.

Prefill is *chunked* (one sequence, ``chunk`` tokens per call): the chunk
writes its K/V into the pages first, then attends over the gathered table
with position masks, which makes intra-chunk causality and attention to
earlier chunks one code path.  The final (ragged) chunk is right-padded;
pad writes land at logical slots the sequence will overwrite at exactly
those positions later, and every read masks by current length, so they
are unobservable.

The ``moe`` family runs the same way with the expert layer
(``models.moe.expert_layer``) in the FFN's place; both programs then return
a fourth value, the step's routing counts summed over layers.  Its
DeepSeek-V2 block (latent attention, ``cfg.mla``) has programs of its own:
the pools hold the latent c_kv and the shared k_pe, carried through the
layers (the dense layers first, then a scan over the expert layers).
Decode attends in the absorbed form; prefill writes the chunk's latent,
then expands keys and values from the table's latent and attends as the
dense family does.

Decode attention (GQA with ``use_pallas`` off, and the latent form) reads
only the live pages: the (slot, page) pairs whose page holds a key some
decoding slot's query sees are listed once a step, and each layer runs one
split-K loop over them, ``DECODE_PAGE_BLOCK`` pairs a trip, merging each
pair's softmax terms into its slot's by log-sum-exp rescaling.  The trip
count follows the batch's contexts, not ``max_len``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import blocks, moe
from repro.models.api import ModelConfig
from repro.models.transformer import _ffn, embed_inputs, unembed

Array = jax.Array


def _mlp(x: Array, lp: Dict, cfg: ModelConfig, rows):
    """The layer's feed-forward on x [B, S, d]: the dense FFN (no counts),
    or the expert layer over the rows where ``rows`` (B * S values) is
    nonzero and its counts."""
    if cfg.family != "moe":
        return _ffn(x, lp, cfg), None
    B, S, d = x.shape
    y, counts = moe.expert_layer(x.reshape(B * S, d), lp, cfg,
                                 rows.reshape(-1) > 0)
    return y.reshape(B, S, d), counts


def _with_counts(out: Tuple, counts) -> Tuple:
    """Append the counts summed over layers (the expert family only)."""
    if counts is None:
        return out
    return out + (jax.tree_util.tree_map(lambda c: jnp.sum(c, axis=0),
                                         counts),)


DECODE_PAGE_BLOCK = 128       # (slot, page) pairs a trip of decode attention
PREFILL_KEY_BLOCK = 1024      # keys a step of the latent prefill attention


def _live_pages(table: Array, pos: Array, active: Array, page: int,
                window: Optional[int]) -> Tuple:
    """The step's live (slot, page) pairs, slot-major: page j of an active
    slot is live when it holds a key the slot's query at ``pos`` sees
    (``j * page <= pos`` and, with a window, a key above ``pos - window``).
    -> (slot, page index, physical page) [N] each and the traced count
    ``n_live``; N covers every pair in whole trips, and the pairs from
    ``n_live`` on repeat the last slot's last page, which the loop masks."""
    S, maxp = table.shape
    j = jnp.arange(maxp, dtype=jnp.int32)
    live = (active[:, None] > 0) & (j * page <= pos[:, None])
    if window is not None:
        live &= (j + 1) * page > pos[:, None] - window + 1
    live = live.reshape(-1)
    N = -(-S * maxp // DECODE_PAGE_BLOCK) * DECODE_PAGE_BLOCK
    (pair,) = jnp.nonzero(live, size=N, fill_value=S * maxp - 1)
    slot, idx = pair // maxp, pair % maxp
    return slot, idx, table[slot, idx], jnp.sum(live, dtype=jnp.int32)


def _split_k_attention(pairs: Tuple, pos: Array, page: int,
                       window: Optional[int], heads: Tuple[int, ...], dv: int,
                       score, value) -> Array:
    """Decode attention over the live pairs of ``_live_pages``: a loop of
    ``DECODE_PAGE_BLOCK`` pairs a trip.  ``score(slot [W], pages [W])``
    gathers the pages and returns the float32 scores [W, *heads, page] of
    each pair's slot's query against them (softmax scale applied) and the
    gathered values; ``value(e, values)`` returns the weighted value sums
    [W, *heads, dv] of the float32 weights e.  Each pair's max, exp-sum and
    value sum merge into its slot's running (m, l, acc) by log-sum-exp
    rescaling; keys past ``pos`` or outside the window are masked, as are
    the pairs past ``n_live``.  -> float32 [S, *heads, dv]; slots with no
    pair read 0."""
    slot, idx, phys, n_live = pairs
    S, W = pos.shape[0], DECODE_PAGE_BLOCK
    key = jnp.arange(page, dtype=jnp.int32)
    seg = dict(num_segments=S, indices_are_sorted=True)

    def trip(t, carry):
        m, l, acc = carry
        at = t * W
        sl, pj, pp = (lax.dynamic_slice_in_dim(a, at, W)
                      for a in (slot, idx, phys))
        k_pos = pj[:, None] * page + key                             # [W,page]
        q_pos = pos[sl][:, None]
        ok = (at + jnp.arange(W) < n_live)[:, None] & (k_pos <= q_pos)
        if window is not None:
            ok &= k_pos > q_pos - window
        s, vals = score(sl, pp)
        ok = ok.reshape((W,) + (1,) * len(heads) + (page,))
        s = jnp.where(ok, s, blocks.NEG_INF)
        mb = jnp.max(s, axis=-1)                                # [W, *heads]
        e = jnp.where(ok, jnp.exp(s - mb[..., None]), 0.0)
        m_new = jnp.maximum(m, jax.ops.segment_max(mb, sl, **seg))
        w = jnp.exp(mb - m_new[sl])
        corr = jnp.exp(m - m_new)
        l = l * corr + jax.ops.segment_sum(jnp.sum(e, axis=-1) * w, sl, **seg)
        acc = acc * corr[..., None] + jax.ops.segment_sum(
            value(e, vals) * w[..., None], sl, **seg)
        return m_new, l, acc

    init = (jnp.full((S,) + heads, blocks.NEG_INF, jnp.float32),
            jnp.zeros((S,) + heads, jnp.float32),
            jnp.zeros((S,) + heads + (dv,), jnp.float32))
    _, l, acc = lax.fori_loop(0, (n_live + W - 1) // W, trip, init)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _paged_gqa_attention(q: Array, kp: Array, vp: Array, pairs: Tuple,
                         pos: Array, cfg: ModelConfig) -> Array:
    """One decode query a slot, q [S, 1, H, D], against the live pages of
    the layer's pools kp, vp [P, page, Hkv, D]: masked attention as
    ``_gather_attention`` computes it over the densified table, reading
    only the live pages.  -> [S, 1, H, D]"""
    S, _, H, D = q.shape
    page, Hkv = kp.shape[1], kp.shape[2]
    qg = q.reshape(S, Hkv, H // Hkv, D).astype(kp.dtype)
    scale = 1.0 / D ** 0.5

    def score(sl, pp):
        k = kp[pp]                                             # [W,page,Hkv,D]
        s = jnp.einsum("whgd,wkhd->whgk", qg[sl], k,
                       preferred_element_type=jnp.float32)
        return s * scale, vp[pp]

    def value(e, v):
        return jnp.einsum("whgk,wkhd->whgd", e.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    o = _split_k_attention(pairs, pos, page, cfg.attn_window,
                           (Hkv, H // Hkv), D, score, value)
    return o.reshape(S, 1, H, D).astype(q.dtype)


def _gather_attention(q: Array, kp: Array, vp: Array, table: Array,
                      q_positions: Array, written: Array,
                      cfg: ModelConfig) -> Array:
    """Prefill's attention: densify the pool rows named by ``table`` and run
    the shared masked attention.  ``written`` [B] = logical slots written so
    far; slots beyond it hold stale pool data and are masked out."""
    B = q.shape[0]
    page = kp.shape[1]
    C = table.shape[1] * page
    written = jnp.broadcast_to(jnp.atleast_1d(written), (B,))
    kd = kp[table].reshape(B, C, *kp.shape[2:])
    vd = vp[table].reshape(B, C, *vp.shape[2:])
    slot = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    k_pos = jnp.where(slot < written[:, None], slot, -(2 ** 30))
    return blocks.attention(q, kd, vd, q_positions=q_positions,
                            k_positions=k_pos, causal=True,
                            window=cfg.attn_window,
                            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)


def paged_decode_step(params: Dict, cfg: ModelConfig, k_pages: Array,
                      v_pages: Array, block_tables: Array, token: Array,
                      pos: Array, active: Array) -> Tuple:
    """One decode token for every slot: token [S], pos [S], active [S] →
    (logits [S, padded_vocab], k_pages, v_pages), and for the ``moe``
    family the routing counts of the active rows.

    ``block_tables`` is the FULL host table (the engine keeps a cached
    device copy and re-uploads it only when the allocator dirtied it);
    ``active`` masks the slots decoding this step.  Inactive slots ride
    along with pos=0 and their table row zeroed *here* — writes land in
    the null page and their logits are garbage the engine discards — so
    the cached table never needs per-step editing on the host.  Attention
    reads the pages of the active slots' contexts only (``_live_pages``).
    """
    if cfg.mla:
        return _mla_decode_step(params, cfg, k_pages, v_pages, block_tables,
                                token, pos, active)
    S = token.shape[0]
    page = k_pages.shape[2]
    block_tables = jnp.where(active[:, None] > 0, block_tables, 0)
    h = jnp.take(params["embed"], token[:, None], axis=0)          # [S,1,d]
    positions = pos[:, None]
    page_of = block_tables[jnp.arange(S), pos // page]             # [S]
    off = pos % page
    pairs = (None if cfg.use_pallas else
             _live_pages(block_tables, pos, active, page, cfg.attn_window))

    def body(h, xs):
        lp, kp, vp = xs                      # kp: [P, page, Hkv, D]
        x = blocks.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = blocks.qkv_project(x, lp["attn"], cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd)
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
        kp = kp.at[page_of, off].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[page_of, off].set(v[:, 0].astype(vp.dtype))
        if cfg.use_pallas:
            from repro.kernels.paged_attention.ops import \
                paged_decode_attention
            o = paged_decode_attention(q[:, 0], kp, vp, block_tables,
                                       pos + 1,
                                       window=cfg.attn_window)[:, None]
        else:
            o = _paged_gqa_attention(q, kp, vp, pairs, pos, cfg)
        h = h + blocks.out_project(o, lp["attn"])
        x = blocks.rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        y, counts = _mlp(x, lp, cfg, active)
        h = h + y
        return h, (kp, vp, counts)

    h, (nk, nv, counts) = lax.scan(body, h,
                                   (params["layers"], k_pages, v_pages),
                                   unroll=cfg.scan_unroll)
    logits = unembed(params, cfg, h[:, 0])
    return _with_counts((logits, nk, nv), counts)


def paged_prefill_chunk(params: Dict, cfg: ModelConfig, k_pages: Array,
                        v_pages: Array, table_row: Array, tokens: Array,
                        p0: Array, n_valid: Optional[Array] = None) -> Tuple:
    """Process ``tokens`` [chunk] of one sequence starting at absolute
    position ``p0``: (logits [chunk, padded_vocab], k_pages, v_pages), and
    for the ``moe`` family the routing counts of the chunk's first
    ``n_valid`` rows (its tokens; the rest are padding the expert layer
    routes nowhere; None: every row).

    Writes the chunk's K/V into the pages, then attends over the whole
    gathered table — earlier chunks and intra-chunk causality fall out of
    the position masks.  The caller reads the logits row of the last
    *valid* token when the chunk completes the prompt.
    """
    if cfg.mla:
        return _mla_prefill_chunk(params, cfg, k_pages, v_pages, table_row,
                                  tokens, p0, n_valid)
    (C,) = tokens.shape
    page = k_pages.shape[2]
    maxp = table_row.shape[0]
    h = embed_inputs(params, cfg, tokens[None])                     # [1,C,d]
    positions = (p0 + jnp.arange(C, dtype=jnp.int32))[None]         # [1,C]
    pidx = positions[0] // page
    # pad rows can run past the table (p0 + C > maxp·page near max_len);
    # an unclamped gather would alias them onto the LAST real page and the
    # scatter would corrupt valid prompt K/V — route them to the null page
    page_of = jnp.where(pidx < maxp,
                        table_row[jnp.minimum(pidx, maxp - 1)], 0)  # [C]
    off = positions[0] % page

    def body(h, xs):
        lp, kp, vp = xs
        x = blocks.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = blocks.qkv_project(x, lp["attn"], cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd)
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
        kp = kp.at[page_of, off].set(k[0].astype(kp.dtype))
        vp = vp.at[page_of, off].set(v[0].astype(vp.dtype))
        o = _gather_attention(q, kp, vp, table_row[None], positions,
                              p0 + C, cfg)
        h = h + blocks.out_project(o, lp["attn"])
        x = blocks.rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        y, counts = _mlp(x, lp, cfg, valid)
        h = h + y
        return h, (kp, vp, counts)

    valid = (None if cfg.family != "moe"
             else _chunk_rows(C, n_valid)[None])
    h, (nk, nv, counts) = lax.scan(body, h,
                                   (params["layers"], k_pages, v_pages),
                                   unroll=cfg.scan_unroll)
    logits = unembed(params, cfg, h[0])
    return _with_counts((logits, nk, nv), counts)


def _chunk_rows(C: int, n_valid) -> Array:
    """[C] rows of a prefill chunk that hold tokens."""
    rows = jnp.ones((C,), bool)
    return rows if n_valid is None else jnp.arange(C) < n_valid


def _page_slots(positions: Array, table: Array, page: int
                ) -> Tuple[Array, Array]:
    """Physical (page, offset) of each logical position [B, S] under the
    rows ``table`` [B, maxp]; positions past the table go to the null page
    (an unclamped gather would alias them onto the last real page)."""
    maxp = table.shape[1]
    pidx = positions // page
    rows = jnp.arange(table.shape[0])[:, None]
    page_of = jnp.where(pidx < maxp, table[rows, jnp.minimum(pidx, maxp - 1)],
                        0)
    return page_of, positions % page


def _mla_layers(params: Dict, cfg: ModelConfig, h: Array, pools: Tuple,
                attend, valid: Array) -> Tuple:
    """The DeepSeek-V2 stack over h [B, S, d]: the dense layers, then a scan
    over the expert layers.  ``attend(x, lp, c_pages, pe_pages, layer)``
    writes the layer's latent and returns (attention output, pools).
    Returns (h, pools, counts summed over the expert layers)."""
    B, S, d = h.shape

    def attention(h, pools, lp, li):
        x = blocks.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        o, pools = attend(x, lp["attn"], *pools, li)
        h = h + blocks.out_project(o, lp["attn"])
        return h, pools, blocks.rms_norm(h, lp["ffn_norm"], cfg.norm_eps)

    def dense(carry, xs):
        h, pools, x = attention(*carry, *xs)
        return (h + blocks.swiglu(x, xs[0]["ffn"]), pools), None

    def expert(carry, xs):
        h, pools, x = attention(*carry, *xs)
        y, counts = moe.expert_layer(x.reshape(B * S, d), xs[0], cfg,
                                     valid.reshape(-1))
        return (h + y.reshape(B, S, d), pools), counts

    K = cfg.first_k_dense
    carry = (h, pools)
    if K:
        carry, _ = lax.scan(dense, carry,
                            (params["dense_layers"], jnp.arange(K)))
    (h, pools), counts = lax.scan(
        expert, carry, (params["layers"], jnp.arange(K, cfg.n_layers)),
        unroll=cfg.scan_unroll)
    return h, pools, jax.tree_util.tree_map(lambda c: jnp.sum(c, 0), counts)


def _mla_decode_step(params: Dict, cfg: ModelConfig, c_pages: Array,
                     pe_pages: Array, block_tables: Array, token: Array,
                     pos: Array, active: Array) -> Tuple:
    """``paged_decode_step`` for latent attention: each slot's query
    attends in the absorbed form (``blocks.mla_absorbed_attention``'s
    arithmetic) to the latent of its live pages."""
    page = c_pages.shape[2]
    block_tables = jnp.where(active[:, None] > 0, block_tables, 0)
    h = jnp.take(params["embed"], token[:, None], axis=0)          # [S,1,d]
    positions = pos[:, None]
    page_of, off = _page_slots(positions, block_tables, page)
    pairs = _live_pages(block_tables, pos, active, page, cfg.attn_window)

    def attend(x, p, cp, pp, li):
        q_nope, q_pe, c_kv, k_pe = blocks.mla_project(x, p, positions, cfg)
        cp = cp.at[li, page_of, off].set(c_kv[:, :, None].astype(cp.dtype))
        pp = pp.at[li, page_of, off].set(k_pe[:, :, None].astype(pp.dtype))
        o = _paged_mla_attention(q_nope[:, 0], q_pe[:, 0], cp, pp, li, pairs,
                                 pos, p, cfg)
        return o[:, None], (cp, pp)

    h, (nc, npe), counts = _mla_layers(params, cfg, h, (c_pages, pe_pages),
                                       attend, active[:, None] > 0)
    return unembed(params, cfg, h[:, 0]), nc, npe, counts


def _paged_mla_attention(q_nope: Array, q_pe: Array, cp: Array, pp: Array,
                         li, pairs: Tuple, pos: Array, p: Dict,
                         cfg: ModelConfig) -> Array:
    """The absorbed latent attention of one decode query a slot, q_nope
    [S, H, dn] and q_pe [S, H, dr], against the live pages of layer ``li``
    of the latent pools cp [L, P, page, 1, r] and pp [L, P, page, 1, dr]: the
    arithmetic of ``blocks.mla_absorbed_attention`` over the densified
    table, reading only the live pages.  -> [S, H, dv]"""
    H, r = q_nope.shape[1], cp.shape[-1]
    scale = blocks.mla_softmax_scale(cfg)
    with jax.named_scope("mla.absorb"):
        q_abs = jnp.einsum("shn,rhn->shr", q_nope, p["w_uk"],
                           preferred_element_type=jnp.float32)
    q_abs, q_pe = q_abs.astype(cp.dtype), q_pe.astype(pp.dtype)

    def score(sl, pages):
        c, pe = cp[li, pages, :, 0], pp[li, pages, :, 0]   # [W,page,r|dr]
        s = (jnp.einsum("whr,wkr->whk", q_abs[sl], c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("whp,wkp->whk", q_pe[sl], pe,
                          preferred_element_type=jnp.float32))
        return s * scale, c

    def value(e, c):
        return jnp.einsum("whk,wkr->whr", e.astype(c.dtype), c,
                          preferred_element_type=jnp.float32)

    with jax.named_scope("mla.attend"):
        ctx = _split_k_attention(pairs, pos, cp.shape[2], cfg.attn_window,
                                 (H,), r, score, value)
    with jax.named_scope("mla.absorb"):
        return jnp.einsum("shr,rhv->shv", ctx.astype(q_nope.dtype), p["w_uv"])


def _mla_prefill_attention(q: Array, cp: Array, pp: Array, li, table_row: Array,
                           q_pos: Array, end, p: Dict, cfg: ModelConfig) -> Array:
    """One sequence's chunk q [C, H, dn+dr] at q_pos [C] against its keys
    below ``end``: a loop over blocks of the table's keys, each block's
    latent gathered from layer ``li`` of the pools and expanded to keys and
    values, the softmax kept exact by rescaling as each block comes in.
    Blocks past ``end`` are not visited.  -> [C, H, dv]"""
    C, H, _ = q.shape
    page = cp.shape[2]
    per = max(1, PREFILL_KEY_BLOCK // page)           # pages a block
    block = per * page
    maxp = table_row.shape[0]
    scale = blocks.mla_softmax_scale(cfg)

    def key_block(j, carry):
        acc, m, l = carry
        pidx = j * per + jnp.arange(per)
        pages = jnp.where(pidx < maxp, table_row[jnp.minimum(pidx, maxp - 1)],
                          0)
        k_pos = j * block + jnp.arange(block)
        with jax.named_scope("mla.absorb"):
            k, v = blocks.mla_expand(cp[li, pages].reshape(1, block, -1),
                                     pp[li, pages].reshape(1, block, -1), p)
        with jax.named_scope("mla.attend"):
            s = jnp.einsum("qhd,khd->hqk", q, k[0].astype(q.dtype),
                           preferred_element_type=jnp.float32) * scale
            ok = (k_pos[None, :] <= q_pos[:, None]) & (k_pos < end)[None, :]
            s = jnp.where(ok[None], s, blocks.NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            e = jnp.where(ok[None], jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "hqk,khv->hqv", e.astype(v.dtype), v[0],
                preferred_element_type=jnp.float32)
            return acc, m_new, l * corr + jnp.sum(e, axis=-1)

    init = (jnp.zeros((H, C, cfg.v_head_dim), jnp.float32),
            jnp.full((H, C), blocks.NEG_INF, jnp.float32),
            jnp.zeros((H, C), jnp.float32))
    acc, _, l = lax.fori_loop(0, (end + block - 1) // block, key_block, init)
    with jax.named_scope("mla.attend"):
        o = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(o, 0, 1).astype(q.dtype)


def _mla_prefill_chunk(params: Dict, cfg: ModelConfig, c_pages: Array,
                       pe_pages: Array, table_row: Array, tokens: Array,
                       p0: Array, n_valid) -> Tuple:
    """``paged_prefill_chunk`` for latent attention: the chunk writes its
    latent, then attends in the expanded form to keys and values made from
    the latent of the table's blocks up to the chunk's end."""
    (C,) = tokens.shape
    page = c_pages.shape[2]
    h = embed_inputs(params, cfg, tokens[None])                     # [1,C,d]
    positions = (p0 + jnp.arange(C, dtype=jnp.int32))[None]         # [1,C]
    page_of, off = _page_slots(positions, table_row[None], page)

    def attend(x, p, cp, pp, li):
        q_nope, q_pe, c_kv, k_pe = blocks.mla_project(x, p, positions, cfg)
        cp = cp.at[li, page_of, off].set(c_kv[:, :, None].astype(cp.dtype))
        pp = pp.at[li, page_of, off].set(k_pe[:, :, None].astype(pp.dtype))
        q = jnp.concatenate([q_nope, q_pe], -1)[0]
        o = _mla_prefill_attention(q, cp, pp, li, table_row, positions[0],
                                   p0 + C, p, cfg)
        return o[None], (cp, pp)

    h, (nc, npe), counts = _mla_layers(params, cfg, h, (c_pages, pe_pages),
                                       attend, _chunk_rows(C, n_valid)[None])
    return unembed(params, cfg, h[0]), nc, npe, counts
