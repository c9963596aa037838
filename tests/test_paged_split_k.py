"""Paged decode attention over the live pages only: the split-K loop over a
step's (slot, page) pairs gives what the densified table gives.

Both attention forms (GQA through ``_gather_attention``, the absorbed latent
form through ``blocks.mla_absorbed_attention``) are computed over the whole
densified table and compared, float32 on the CPU, with the loop that visits
only the live pairs, on ragged batches: a slot at length 1, lengths on and
off page boundaries, idle slots, a live-pair count that is not a whole
number of trips, rows that share pages (as forked siblings do) so that
the live pairs outnumber the pool's pages, and a sliding window.  The
engine's ``decode_pages_read`` counts exactly the pairs the loop visits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.tasks import MathTask, Tokenizer
from repro.models import blocks
from repro.models.api import ModelConfig, get_model
from repro.rl.rollout import GenConfig
from repro.rl.weight_sync import WeightStore
from repro.serve import PagedEngine, ServeConfig
from repro.serve import model as paged

PAGE, MAXP = 4, 40
# trips of 16 pairs cross slots and end ragged at these sizes; the module's
# own width runs these batches in one trip
WIDTHS = sorted({16, paged.DECODE_PAGE_BLOCK})
GQA = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=259,
                  dtype="float32", remat=False)
MLA = get_smoke_config("deepseek-v2-lite")

# name -> (context lengths, active, rows share one table, window); where
# the last slot reaches its last page, the padding pairs (which repeat that
# page) must be masked
BATCHES = {
    "ragged": ([1, 4, 5, 57, 81, 33, 160], [1] * 7, False, None),
    "idle_slots": ([1, 4, 5, 57, 160, 81, 33], [1, 0, 1, 1, 0, 1, 0],
                   False, None),
    "shared_pages": ([150, 149, 97, 160, 64, 1, 130], [1] * 7, True, None),
    "window": ([1, 4, 5, 57, 81, 33, 160], [1, 1, 1, 0, 1, 1, 1], False, 10),
    "window_past_context": ([1, 4, 5, 57, 160, 81, 33], [1] * 7, False, 70),
}


def _batch(name, seed=0):
    """Tables, decode positions and active flags of one ragged batch; each
    slot's pages are distinct (or, shared, one table for every row, over a
    pool of fewer pages than the batch's live pairs)."""
    lengths, active, shared, window = BATCHES[name]
    S = len(lengths)
    rng = np.random.default_rng(seed)
    if shared:
        P = 1 + MAXP
        table = np.tile(rng.permutation(np.arange(1, P)), (S, 1))
    else:
        P = 1 + S * MAXP
        table = rng.permutation(np.arange(1, P)).reshape(S, MAXP)
    pos = np.asarray(lengths) - 1
    active = np.asarray(active)
    table = np.where(active[:, None] > 0, table, 0)   # as the decode step does
    return (P, jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(active, jnp.int32), window)


@pytest.fixture(params=WIDTHS, ids=lambda w: f"W{w}")
def width(request, monkeypatch):
    monkeypatch.setattr(paged, "DECODE_PAGE_BLOCK", request.param)
    return request.param


def _pairs(table, pos, active, window):
    return paged._live_pages(table, pos, active, PAGE, window)


def _live_count(pos, active, window):
    """Pages of each active slot's context its query sees."""
    written = np.asarray(pos) + 1
    first = np.maximum(written - window, 0) // PAGE if window else 0
    pages = -(-written // PAGE) - first
    return int(np.sum(pages * (np.asarray(active) > 0)))


@pytest.mark.parametrize("name", sorted(BATCHES))
def test_live_pairs_are_the_pages_each_query_sees(name, width):
    P, table, pos, active, window = _batch(name)
    slot, idx, phys, n_live = _pairs(table, pos, active, window)
    n = int(n_live)
    assert n == _live_count(pos, active, window)
    assert len(slot) % width == 0 and len(slot) >= table.size
    slot, idx, phys = (np.asarray(a[:n]) for a in (slot, idx, phys))
    assert np.all(np.diff(slot) >= 0)                       # slot-major
    np.testing.assert_array_equal(phys, np.asarray(table)[slot, idx])
    for s in range(table.shape[0]):
        want = [j for j in range(MAXP) if active[s] and j * PAGE <= pos[s]
                and (window is None or (j + 1) * PAGE > pos[s] - window + 1)]
        assert idx[slot == s].tolist() == want


def test_batches_cover_the_edges():
    """The cases above hold, at the narrowest width, a live-pair count that
    is not a whole number of trips and takes more than one, and live pairs
    beyond the pool."""
    counts = {n: int(_pairs(*_batch(n)[1:4], _batch(n)[4])[3])
              for n in BATCHES}
    W = WIDTHS[0]
    assert counts["ragged"] > W and counts["ragged"] % W
    P = _batch("shared_pages")[0]
    assert counts["shared_pages"] > P


def _densified_positions(pos, T):
    written = pos + 1
    slot = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (len(pos), T))
    return jnp.where(slot < written[:, None], slot, -(2 ** 30))


@pytest.mark.parametrize("name", sorted(BATCHES))
def test_gqa_decode_matches_the_densified_table(name, width):
    P, table, pos, active, window = _batch(name)
    cfg = GQA.replace(attn_window=window)
    S, H, Hkv, D = table.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (S, 1, H, D))
    kp = jax.random.normal(k2, (P, PAGE, Hkv, D))
    vp = jax.random.normal(k3, (P, PAGE, Hkv, D))
    got = paged._paged_gqa_attention(q, kp, vp, _pairs(table, pos, active,
                                                      window), pos, cfg)
    want = paged._gather_attention(q, kp, vp, table, pos[:, None], pos + 1,
                                   cfg)
    live = np.asarray(active) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5)
    assert np.all(np.isfinite(np.asarray(got)))


@pytest.mark.parametrize("name", sorted(BATCHES))
def test_latent_decode_matches_the_densified_table(name, width):
    P, table, pos, active, window = _batch(name)
    cfg = MLA.replace(attn_window=window)
    p = jax.tree_util.tree_map(lambda a: a[0], get_model(cfg).init(
        jax.random.PRNGKey(0), cfg)["layers"])["attn"]
    S, H, L, li = table.shape[0], cfg.n_heads, 3, 1
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(2), 4)
    q_nope = jax.random.normal(k1, (S, H, dn))
    q_pe = jax.random.normal(k2, (S, H, dr))
    cp = jax.random.normal(k3, (L, P, PAGE, 1, r))
    pp = jax.random.normal(k4, (L, P, PAGE, 1, dr))
    got = paged._paged_mla_attention(q_nope, q_pe, cp, pp, li,
                                     _pairs(table, pos, active, window), pos,
                                     p, cfg)
    T = MAXP * PAGE
    want = blocks.mla_absorbed_attention(
        q_nope, q_pe, cp[li, table].reshape(S, T, r),
        pp[li, table].reshape(S, T, dr), pos, _densified_positions(pos, T),
        p, cfg)
    live = np.asarray(active) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5)
    assert np.all(np.isfinite(np.asarray(got)))


@pytest.mark.parametrize("window", [None, 6], ids=["full", "window"])
def test_decode_pages_read_counts_the_pairs_the_loop_visits(window):
    """A GRPO group (forked siblings) and a second prompt decode through
    ``PagedEngine.step()``: ``decode_pages_read`` is the sum over decode
    steps of the live pairs ``_live_pages`` lists for the step's inputs,
    which is sum ceil(written / page) over the active slots without a
    window."""
    cfg = GQA.replace(vocab=Tokenizer().vocab_size, attn_window=window)
    store = WeightStore()
    store.publish(get_model(cfg).init(jax.random.PRNGKey(0), cfg))
    eng = PagedEngine(cfg, store,
                      GenConfig(max_new_tokens=9, greedy=True, eos_id=-1),
                      ServeConfig(max_slots=4, page_size=PAGE, prefill_chunk=8,
                                  max_len=32))
    seen = []
    decode = eng._decode

    def recorded(params, cfg_, kp, vp, table, token, pos, active):
        seen.append((np.asarray(table), np.asarray(pos), np.asarray(active)))
        return decode(params, cfg_, kp, vp, table, token, pos, active)

    eng._decode = recorded
    ids = np.random.default_rng(3).integers(3, 200, size=11).tolist()
    eng.submit_group(MathTask(prompt="", answer=0, prompt_ids=ids), 3)
    eng.submit([MathTask(prompt="", answer=0, prompt_ids=ids[:6])])
    eng.drain()
    assert eng.stats.decode_steps == len(seen) > 0
    visited = sum(int(_pairs(jnp.asarray(t), jnp.asarray(p), jnp.asarray(a),
                             window)[3]) for t, p, a in seen)
    assert eng.stats.decode_pages_read == visited
    assert visited == sum(_live_count(p, a, window) for _, p, a in seen)
    if window is None:
        assert visited == sum(int(np.sum(-(-(p + 1) // PAGE) * a))
                              for _, p, a in seen)
    assert visited < eng.stats.decode_slot_steps * eng.kv.maxp
