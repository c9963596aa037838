"""The paged engine's phase scopes and its host-argument counter.

  * Under a ``jax.profiler`` session the engine's ``engine.*`` annotations
    land on the host plane of the ``.xplane.pb``: every ``engine.decode``
    holds exactly one ``engine.dispatch`` and one ``engine.wait`` and lies
    inside an ``engine.step``; the tokens equal those of a run without
    the profiler.
  * The weights stay on the device: ``EngineStats.weight_upload_bytes``
    reads the fetched tree's host bytes once, at construction (0 when the
    store already hands out device arrays), and again at each weight swap;
    ``EngineStats.host_arg_bytes`` grows, per decode call, by the host
    inputs alone.
  * After a swap the engine holds the new version as device arrays and
    decodes with it.
  * ``obs.trace.scope`` records nested spans on a ``Tracer``.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.data.tasks import MathTaskGenerator, Tokenizer
from repro.models.api import ModelConfig, get_model
from repro.obs.trace import Tracer, scope
from repro.rl.rollout import GenConfig
from repro.rl.weight_sync import WeightStore
from repro.serve import PagedEngine, ServeConfig

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=2, d_ff=64,
                   vocab=Tokenizer().vocab_size, dtype="float32", remat=False)
GEN = GenConfig(max_new_tokens=6, eos_id=-1)
SERVE = ServeConfig(max_slots=3, max_len=96, page_size=8, prefill_chunk=8)


def _store(quantize=False):
    store = WeightStore(quantize=quantize)
    store.publish(get_model(TINY).init(jax.random.PRNGKey(0), TINY))
    return store


def _generate(store, per_row):
    eng = PagedEngine(TINY, store, GEN, SERVE, rng_seed=1)
    tasks = MathTaskGenerator(seed=3).batch(3)
    eng.submit(tasks[:2])
    # a request with its own temperature puts every decode step on the
    # per-row sampler
    eng.submit(tasks[2:], temperature=0.7 if per_row else None)
    eng.drain()
    rollouts, _ = eng.collect()
    return [r.completion_ids for r in rollouts], eng.stats


def _engine_events(path):
    """``engine.*`` host events per host thread, as (start, end, name)."""
    from jax.profiler import ProfileData
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    out = []
    for line in plane.lines:
        evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in line.events if e.name.startswith("engine.")]
        if evs:
            out.append(evs)
    return out


@pytest.mark.parametrize("per_row", [False, True], ids=["batched", "per_row"])
def test_phase_scopes_nest_on_the_profiler_host_plane(per_row, tmp_path):
    store = _store()
    want, _ = _generate(store, per_row)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got, stats = _generate(store, per_row)
    finally:
        jax.profiler.stop_trace()
    assert got == want

    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    lines = _engine_events(path)
    assert len(lines) == 1                  # the driving thread only
    evs = lines[0]
    inside = lambda outer, name: [e for e in evs if e[2] == name
                                  and outer[0] <= e[0] and e[1] <= outer[1]]
    decodes = [e for e in evs if e[2] == "engine.decode"]
    assert len(decodes) == stats.decode_steps > 0
    for d in decodes:
        assert len(inside(d, "engine.dispatch")) == 1
        assert len(inside(d, "engine.wait")) == 1
        assert len(inside(d, "engine.sample")) == 1
        assert any(s[0] <= d[0] and d[1] <= s[1]
                   for s in evs if s[2] == "engine.step")
    steps = [e for e in evs if e[2] == "engine.step"]
    assert all(len(inside(s, "engine.admit")) == 1 for s in steps)
    # every prefill chunk dispatches once; the prompt's last one also waits
    # for its first tokens
    prefills = [e for e in evs if e[2] == "engine.prefill"]
    assert prefills and all(len(inside(p, "engine.dispatch")) == 1
                            for p in prefills)
    assert sum(len(inside(p, "engine.wait")) for p in prefills) == 3


def _tree_host_bytes(store):
    params, _ = store.fetch(dtype=TINY.jdtype)
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(params)
               if not isinstance(x, jax.Array))


def _on_device(eng):
    return all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(eng._params))


@pytest.mark.parametrize("quantize", [False, True],
                         ids=["host_params", "device_params"])
def test_host_arg_bytes_per_decode_call(quantize):
    store = _store(quantize)
    tree = _tree_host_bytes(store)
    if quantize:
        assert tree == 0                    # dequantized on the device
    else:
        leaves = jax.tree_util.tree_leaves(store.fetch()[0])
        assert tree == sum(x.size * x.dtype.itemsize for x in leaves) > 0
    eng = PagedEngine(TINY, store, GEN, SERVE, rng_seed=1)
    assert eng.stats.weight_upload_bytes == tree
    assert _on_device(eng)
    eng.submit(MathTaskGenerator(seed=3).batch(3))
    while any(r.state != "DECODE" for r in eng._active.values()) \
            or eng._queue:
        eng.step()
    inputs = 3 * SERVE.max_slots * np.dtype(np.int32).itemsize
    for _ in range(3):
        b0, d0 = eng.stats.host_arg_bytes, eng.stats.decode_steps
        eng.step()
        assert eng.stats.decode_steps == d0 + 1
        assert eng.stats.host_arg_bytes - b0 == inputs
        assert eng.stats.weight_upload_bytes == tree


def test_weight_swap_keeps_the_new_version_on_the_device():
    gen = GenConfig(max_new_tokens=12, segment=2, greedy=True, eos_id=-1)
    tasks = MathTaskGenerator(seed=3).batch(3)
    v2 = get_model(TINY).init(jax.random.PRNGKey(1), TINY)

    def run(swap):
        store = _store()
        eng = PagedEngine(TINY, store, gen, SERVE, rng_seed=1)
        eng.submit(tasks)
        while eng.stats.decode_steps < 3:
            assert eng.step()
        if swap:
            tree = _tree_host_bytes(store)
            swaps, up = eng.stats.weight_swaps, eng.stats.weight_upload_bytes
            store.publish(v2)
            for _ in range(gen.segment):    # past the next segment boundary
                eng.step()
            assert eng.stats.weight_swaps == swaps + 1
            assert eng.stats.weight_upload_bytes == up + tree
            assert _on_device(eng)
            for got, want in zip(jax.tree_util.tree_leaves(eng._params),
                                 jax.tree_util.tree_leaves(store.fetch()[0])):
                np.testing.assert_array_equal(np.asarray(got), want)
        eng.drain()
        rollouts, metrics = eng.collect()
        return [r.completion_ids for r in rollouts], metrics

    kept, m_kept = run(swap=False)
    swapped, m_swapped = run(swap=True)
    assert m_kept["versions"] == [1] and m_swapped["versions"] == [1, 2]
    assert len(swapped) == len(kept) == 3
    # the steps after the swap decode with the second version's weights
    assert swapped != kept


def test_scope_records_nested_spans_on_a_tracer():
    tr = Tracer()
    with scope(tr, "step", queued=2):
        with scope(tr, "decode"):
            pass
    spans = {name: (t, dur, args) for name, t, dur, args
             in tr.spans(group="engine")}
    assert set(spans) == {"step", "decode"}
    assert spans["step"][2] == {"queued": 2}
    (ts, ds, _), (td, dd, _) = spans["step"], spans["decode"]
    assert ts <= td and td + dd <= ts + ds
    assert [name for name, *_ in tr.spans()] == ["decode", "step"]
    with scope(None, "step"):               # no tracer: annotation only
        pass
