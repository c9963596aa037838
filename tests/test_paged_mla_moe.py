"""The expert family in the paged engine: DeepSeek-V2's block (latent
attention over latent pools, leading dense layers, routed and shared
experts) and Qwen3-MoE's (GQA attention, routed experts).

All at ``smoke_config()`` sizes on seeded random weights in float32 on the
CPU, comparing logits (or logps read from them), not sampled tokens.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.deepseek_v2_lite import CONFIG as DSV2_LITE
from repro.data.tasks import MathTask, Tokenizer
from repro.models import blocks, moe
from repro.models.api import ModelConfig, get_model
from repro.rl.rollout import GenConfig, RolloutEngine
from repro.rl.weight_sync import WeightStore
from repro.serve import PagedEngine, ServeConfig
from repro.serve.kv_cache import PagedKVCache
from repro.serve.model import paged_decode_step, paged_prefill_chunk

DSV2 = "deepseek-v2-lite"
QWEN3 = "qwen3-moe-235b-a22b"
# float32 throughout: the paged and full paths differ only in the order of
# their sums (absorbed against expanded attention, sorted against dense
# experts, the pool's page layout), a few ulps of logits up to ~5 in size
# (measured: at most 3e-6); 1e-4 leaves room for that and fails on any term
# left out, as on weights rounded to bfloat16 (they move the full forward's
# logits by 0.02-0.28 at these sizes)
ATOL = 1e-4


def _params(cfg, seed=0):
    return get_model(cfg).init(jax.random.PRNGKey(seed), cfg)


def _full_logits(cfg, params, toks, monkeypatch):
    """The training forward, float32 at the highest precision; its GShard
    capacity is raised so that it drops nothing."""
    monkeypatch.setattr(moe, "CAPACITY_FACTOR", 100.0)
    with jax.default_matmul_precision("highest"):
        return np.asarray(get_model(cfg).forward(params, cfg, toks[None])[0])


@pytest.mark.parametrize("arch", [DSV2, QWEN3])
def test_paged_prefill_then_decode_matches_full_forward(arch, monkeypatch):
    """Two prefill chunks (the second ragged, with pad rows) write the
    pools, then decode steps read them: every logit row matches the full
    forward of the same block."""
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    S, chunk, Sp = 21, 8, 11
    toks = jax.random.randint(jax.random.PRNGKey(1), (S,), 0, cfg.vocab)
    full = _full_logits(cfg, params, toks, monkeypatch)
    kv = PagedKVCache(cfg, max_slots=2, max_len=32, page_size=4)
    slot = kv.alloc_slot()
    assert kv.ensure(slot, S)
    kp, vp = kv.k_pages, kv.v_pages
    row = jnp.asarray(kv.block_tables[slot])
    with jax.default_matmul_precision("highest"):
        for p0 in range(0, Sp, chunk):
            n = min(chunk, Sp - p0)
            t = jnp.zeros((chunk,), jnp.int32).at[:n].set(toks[p0:p0 + n])
            lg, kp, vp, _ = paged_prefill_chunk(params, cfg, kp, vp, row, t,
                                                jnp.int32(p0), jnp.int32(n))
            np.testing.assert_allclose(np.asarray(lg[:n]), full[p0:p0 + n],
                                       atol=ATOL)
        bt = jnp.asarray(kv.block_tables)
        for i in range(Sp, S):
            lg, kp, vp, _ = paged_decode_step(
                params, cfg, kp, vp, bt, jnp.array([toks[i], 0]),
                jnp.array([i, 0]), jnp.array([1, 0]))
            np.testing.assert_allclose(np.asarray(lg[0]), full[i], atol=ATOL)


def _share(cfg, params_layer, i, held):
    """The i-th of the shares of ``held`` experts: its config and params."""
    lp = dict(params_layer, experts=jax.tree_util.tree_map(
        lambda a: a[i * held:(i + 1) * held], params_layer["experts"]))
    return cfg.replace(expert_offset=i * held, experts_held=held), lp


@pytest.mark.parametrize("rows", [10, 100], ids=["dense_form", "sorted_form"])
def test_expert_shares_add_up_to_the_uncut_layer(rows):
    """The expert layer over all 8 shares of the experts, with the shared
    experts counted once, adds up to the layer that holds every expert; the
    held (row, choice) counts add up to all pairs."""
    cfg = get_smoke_config(DSV2)
    lp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.expert_layer(x, lp, cfg)
        shared = blocks.swiglu(x[None], lp["shared"])[0]
        parts, held_pairs = -7.0 * shared, 0
        for i in range(8):
            c, p = _share(cfg, lp, i, cfg.n_experts // 8)
            y, n = moe.expert_layer(x, p, c)
            parts, held_pairs = parts + y, held_pairs + int(n["expert_rows"].sum())
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=ATOL)
    assert held_pairs == int(counts["routed_pairs"]) == rows * cfg.top_k


@pytest.mark.parametrize("rows", [16, 256], ids=["dense_form", "sorted_form"])
def test_router_forced_to_one_expert_drops_no_row(rows):
    """Every row's first choice is expert 0: each row still gets each of
    its chosen experts, computed one row at a time here; the GShard
    training path would keep ~capacity rows of them."""
    cfg = get_smoke_config(DSV2)
    lp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["layers"])
    lp["router"] = lp["router"].at[:, 0].set(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (rows, cfg.d_model)))
    with jax.default_matmul_precision("highest"):
        y, counts = moe.expert_layer(x, lp, cfg)
        w, idx = jax.lax.top_k(jax.nn.softmax(x @ lp["router"], axis=-1),
                               cfg.top_k)
        ex = lp["experts"]
        want = blocks.swiglu(x[None], lp["shared"])[0]
        for k in range(cfg.top_k):
            e = idx[:, k]
            h = jax.nn.silu(jnp.einsum("nd,ndf->nf", x, ex["w_gate"][e])) * \
                jnp.einsum("nd,ndf->nf", x, ex["w_up"][e])
            want = want + w[:, k:k + 1] * jnp.einsum("nf,nfd->nd", h,
                                                     ex["w_down"][e])
    assert np.all(np.asarray(idx[:, 0]) == 0)
    assert int(counts["expert_rows"][0]) == rows
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=ATOL)


def _engine(cfg, store, **serve):
    gen = GenConfig(max_new_tokens=12, greedy=True, eos_id=-1)
    return PagedEngine(cfg, store, gen,
                       ServeConfig(max_slots=4, page_size=4, prefill_chunk=8,
                                   **serve))


def _task(n, seed):
    ids = np.random.default_rng(seed).integers(3, 200, size=n).tolist()
    return MathTask(prompt="", answer=0, prompt_ids=ids)


def test_latent_pools_keep_logits_through_fork_preemption_and_resume():
    """A GRPO group forks its prompt's latent pages (COW on the shared tail
    page) in a pool too small for the whole group, so siblings are
    preempted and resume by recompute; every sibling's logps match a
    sibling decoded alone in an ample pool."""
    cfg = get_smoke_config(DSV2).replace(vocab=Tokenizer().vocab_size)
    store = WeightStore()
    store.publish(_params(cfg))
    task = _task(13, seed=5)
    alone, _ = _engine(cfg, store, max_len=25).generate([task])
    plen = len(task.prompt_ids)
    # the null page, the prompt's pages and one sibling's whole context
    pages = 1 + (plen + 3) // 4 + (plen + 12 + 3) // 4
    eng = _engine(cfg, store, max_len=25, num_pages=pages)
    eng.submit_group(task, 4)
    eng.drain()
    rollouts, m = eng.collect()
    assert m["forks"] >= 1 and m["cow_copies"] >= 1 and m["preemptions"] >= 1
    assert len(rollouts) == 4
    for r in rollouts:
        assert r.completion_ids == alone[0].completion_ids
        np.testing.assert_allclose(r.behavior_logp, alone[0].behavior_logp,
                                   atol=ATOL)


def test_qwen3_moe_runs_paged_and_matches_static_engine(monkeypatch):
    """The GQA expert config runs through ``PagedEngine.step()`` (the
    expert layer in the FFN's place) and matches the static engine's logps
    of the same greedy tokens; the static engine's GShard capacity is
    raised so that it drops nothing."""
    monkeypatch.setattr(moe, "CAPACITY_FACTOR", 100.0)
    cfg = get_smoke_config(QWEN3).replace(vocab=Tokenizer().vocab_size)
    store = WeightStore()
    store.publish(_params(cfg))
    tasks = [_task(9, seed=1), _task(14, seed=2)]
    paged, m = _engine(cfg, store, max_len=32).generate(tasks)
    gen = GenConfig(max_new_tokens=12, greedy=True, eos_id=-1)
    for task, got in zip(tasks, paged):
        want, _ = RolloutEngine(cfg, store, gen).generate([task])
        assert got.completion_ids == want[0].completion_ids
        np.testing.assert_allclose(got.behavior_logp, want[0].behavior_logp,
                                   atol=ATOL)
    assert m["decode_steps"] > 0


def test_routing_counters_count_held_and_all_pairs():
    """``EngineStats.expert_rows`` and ``routed_pairs`` count the (row,
    choice) pairs of every prefilled and decoded token, each expert layer:
    with every expert held they agree; with one share, the held experts get
    part; the dense family counts nothing."""
    base = get_smoke_config(DSV2).replace(vocab=Tokenizer().vocab_size)
    task = _task(11, seed=3)
    for cfg in (base, base.replace(experts_held=4, expert_offset=8)):
        store = WeightStore()
        store.publish(_params(cfg))
        eng = _engine(cfg, store, max_len=24)
        rollouts, m = eng.generate([task])
        st = eng.stats
        tokens = m["prefill_tokens"] + m["decode_steps"]
        assert st.routed_pairs == tokens * cfg.top_k * (cfg.n_layers
                                                        - cfg.first_k_dense)
        assert len(st.expert_rows) == cfg.held_experts
        if cfg.experts_held:
            assert 0 < sum(st.expert_rows) < st.routed_pairs
        else:
            assert sum(st.expert_rows) == st.routed_pairs
    dense = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                        n_heads=4, n_kv_heads=2, d_ff=64,
                        vocab=Tokenizer().vocab_size, dtype="float32",
                        remat=False)
    store = WeightStore()
    store.publish(_params(dense))
    eng = _engine(dense, store, max_len=24)
    eng.generate([task])
    assert eng.stats.expert_rows == () and eng.stats.routed_pairs == 0


# sha256 of the jaxpr text of the dense family's paged programs at this
# size (jax 0.9.0): prefill as traced before the expert family entered it,
# decode since its attention reads only the live pages (the split-K loop at
# ``DECODE_PAGE_BLOCK`` 128).  The dense path must trace to the same
# computation; a deliberate change to the dense paged programs updates these.
DENSE_JAXPRS = {
    ("tiny", "decode"): "a02a5dc3c6fd4cd5288d3b9e3239db094407e1f69c72160906dd8350a99718f6",
    ("tiny", "prefill"): "cd99c11c514c703fa25a77cdb0f7836aa5f1f088b91e1832cb3c91fd5cdfb6aa",
    ("window", "decode"): "d5785a7f1c604180d520cdf869d1e059df260743b90df2c4c9024843984a7141",
    ("window", "prefill"): "5b5fb5c4f95f82893ce2b8dce87e98186c221e9fec774b0366b5e599d7b71e1f",
}


@pytest.mark.parametrize("name", ["tiny", "window"])
def test_dense_paged_programs_trace_unchanged(name):
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=259,
                      dtype="float32", remat=False)
    if name == "window":
        cfg = cfg.replace(name="w", qkv_bias=True, attn_window=16)
    params = jax.eval_shape(lambda: _params(cfg))
    kv = PagedKVCache(cfg, max_slots=3, max_len=40, page_size=8)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    traced = {
        "decode": jax.make_jaxpr(paged_decode_step, static_argnums=1)(
            params, cfg, kv.k_pages, kv.v_pages, i32(3, kv.maxp), i32(3),
            i32(3), i32(3)),
        "prefill": jax.make_jaxpr(paged_prefill_chunk, static_argnums=1)(
            params, cfg, kv.k_pages, kv.v_pages, i32(kv.maxp), i32(16),
            i32()),
    }
    for program, jaxpr in traced.items():
        digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()
        assert digest == DENSE_JAXPRS[(name, program)], program


# The same programs' logits, as the dense family's paged programs computed
# them before the expert family entered them: prefill of a 20-token prompt
# in two 16-token chunks (the last valid row of each), then four decode
# steps of slot 0 beside two idle slots; the first six logits of each.
# Unlike the jaxpr pin, this holds across versions of jax: float32 on the
# CPU, the same computation gives the same numbers to a few ulps, and
# 1e-5 leaves room for another compiler's order of sums (logits ~1).
DENSE_LOGITS = {
    "tiny": [
        [0.2608010, 0.9244651, -0.1286055, 0.6015854, 1.1033256, -1.6684718],
        [0.6902257, -0.1816632, 0.6952273, 0.9830134, 0.3567173, -0.0885331],
        [0.7078158, 0.0078898, 0.2630349, 0.7006505, 0.4633042, 0.1672128],
        [-0.1143154, 0.0949798, -0.7176433, -0.2161117, 0.3462933, -0.5524881],
        [0.1071155, 1.0071845, -0.3974991, 0.5034109, 1.3709254, -0.0890176],
        [-0.2470562, -0.2848275, -0.4850042, 1.3139098, -0.7324632, -0.9052671],
    ],
    "window": [
        [0.2608010, 0.9244651, -0.1286055, 0.6015854, 1.1033256, -1.6684718],
        [1.1100798, 0.2505130, 0.8422200, 0.8803649, 0.3349981, 0.4036128],
        [0.5795915, 0.5831234, 0.4308251, 0.6577284, 0.4025445, 0.3361239],
        [-0.1480077, 0.5778775, 0.1956404, 0.7411924, -0.0756130, -0.7427505],
        [0.1733740, 1.0209719, 0.3598689, 0.5327585, 1.1075982, 1.1070442],
        [-0.6977801, 1.3232399, -0.0736091, 0.4098248, 1.9864876, -0.0522786],
    ],
}


@pytest.mark.parametrize("name", ["tiny", "window"])
def test_dense_paged_programs_give_the_same_logits(name):
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=259,
                      dtype="float32", remat=False)
    if name == "window":
        cfg = cfg.replace(name="w", qkv_bias=True, attn_window=16)
    params = _params(cfg)
    kv = PagedKVCache(cfg, max_slots=3, max_len=40, page_size=8)
    k, v = kv.k_pages, kv.v_pages
    table = np.zeros((3, kv.maxp), np.int32)
    table[0] = np.arange(1, kv.maxp + 1)
    toks = np.random.default_rng(0).integers(0, 259, size=24)
    prefill = jax.jit(paged_prefill_chunk, static_argnums=1)
    decode = jax.jit(paged_decode_step, static_argnums=1)
    got = []
    for p0 in (0, 16):
        n = min(16, 20 - p0)
        chunk = np.zeros(16, np.int32)
        chunk[:n] = toks[p0:p0 + n]
        logits, k, v = prefill(params, cfg, k, v, jnp.asarray(table[0]),
                               jnp.asarray(chunk), jnp.int32(p0))
        got.append(np.asarray(logits[n - 1, :6]))
    for pos in range(20, 24):
        logits, k, v = decode(params, cfg, k, v, jnp.asarray(table),
                              jnp.asarray([toks[pos - 1], 0, 0], jnp.int32),
                              jnp.asarray([pos, 0, 0], jnp.int32),
                              jnp.asarray([1, 0, 0], jnp.int32))
        got.append(np.asarray(logits[0, :6]))
    np.testing.assert_allclose(np.stack(got), np.asarray(DENSE_LOGITS[name]),
                               atol=1e-5)


def test_yarn_frequencies_and_softmax_scale_at_published_sizes():
    """DeepSeek-V2-Lite's rope: pairs below the low correction bound (10)
    keep theta's frequencies, pairs from the high bound (23) on are divided
    by 40, a linear ramp between; the softmax scale is
    192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2."""
    cfg = DSV2_LITE
    ratio = np.asarray(blocks.yarn_inv_freq(64, 1e4, cfg.rope_scaling)
                       / blocks.rope_freqs(64, 1e4))
    np.testing.assert_allclose(ratio[:11], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[23:], 1 / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(ratio[11:23], 1 - ramp + ramp / 40, rtol=1e-5)
    assert blocks.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2, rel=1e-12)
    assert blocks.mla_softmax_scale(cfg) == pytest.approx(0.114722, abs=1e-6)


def test_latent_kv_bytes_in_the_scheduler_spec():
    """The scheduler's cost model prices a latent cache at its width, 512 +
    64 values a layer a token (31,104 B in bfloat16), not
    2 * n_kv_heads * head_dim; a GQA model's count is unchanged."""
    assert DSV2_LITE.spec.kv_bytes_per_token(2) == 27 * 576 * 2 == 31104
    qd = get_smoke_config("qwen-distill-1.5b")
    assert qd.spec.kv_bytes_per_token(2) == 2 * qd.n_layers * qd.n_kv_heads \
        * qd.hd * 2
