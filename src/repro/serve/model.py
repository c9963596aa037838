"""Paged forward passes for the dense-transformer and expert families.

Mirrors ``models/transformer.py``'s prefill/decode math exactly (same
blocks, same rope, same masked-softmax attention semantics) but reads and
writes the **paged** cache: per step, new K/V land at logical slot
``pos`` → physical ``(table[pos // page], pos % page)``, and attention
runs either through the Pallas ``paged_attention`` kernel
(``cfg.use_pallas``) or a gather + ``blocks.attention`` reference path
whose extra pool slots are exactly masked — so a paged greedy decode is
token-identical to the dense engine's.

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
Decode attends in the absorbed form over the densified table; prefill
writes the chunk's latent, then expands keys and values from the table's
latent and attends as the dense family does.
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


def _key_positions(written: Array, B: int, C: int) -> Array:
    """[B, C] position of each densified table slot, masked (-2^30) at and
    beyond ``written`` [B] (stale pool data), as ``_gather_attention``
    masks them."""
    written = jnp.broadcast_to(jnp.atleast_1d(written), (B,))
    slot = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    return jnp.where(slot < written[:, None], slot, -(2 ** 30))


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


def _gather_attention(q: Array, kp: Array, vp: Array, table: Array,
                      q_positions: Array, written: Array,
                      cfg: ModelConfig) -> Array:
    """Reference path: densify the pool rows named by ``table`` and run the
    shared masked attention.  ``written`` [B] = logical slots written so
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
    the cached table never needs per-step editing on the host.
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
            o = _gather_attention(q, kp, vp, block_tables, positions,
                                  pos + 1, cfg)
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
    attends in the absorbed form to the latent of its densified table."""
    S = token.shape[0]
    page = c_pages.shape[2]
    T = block_tables.shape[1] * page
    block_tables = jnp.where(active[:, None] > 0, block_tables, 0)
    h = jnp.take(params["embed"], token[:, None], axis=0)          # [S,1,d]
    positions = pos[:, None]
    page_of, off = _page_slots(positions, block_tables, page)
    k_pos = _key_positions(pos + 1, S, T)

    def attend(x, p, cp, pp, li):
        q_nope, q_pe, c_kv, k_pe = blocks.mla_project(x, p, positions, cfg)
        cp = cp.at[li, page_of, off].set(c_kv[:, :, None].astype(cp.dtype))
        pp = pp.at[li, page_of, off].set(k_pe[:, :, None].astype(pp.dtype))
        with jax.named_scope("mla.attend"):      # the densified table
            cd = cp[li, block_tables].reshape(S, T, -1)
            ped = pp[li, block_tables].reshape(S, T, -1)
        o = blocks.mla_absorbed_attention(q_nope[:, 0], q_pe[:, 0], cd, ped,
                                          pos, k_pos, p, cfg)
        return o[:, None], (cp, pp)

    h, (nc, npe), counts = _mla_layers(params, cfg, h, (c_pages, pe_pages),
                                       attend, active[:, None] > 0)
    return unembed(params, cfg, h[:, 0]), nc, npe, counts


PREFILL_KEY_BLOCK = 1024      # keys a step of the latent prefill attention


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
