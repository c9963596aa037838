"""The rollout driver, its reference and its check, at smoke size on the CPU.

The driver runs end to end here through its ``run`` function (the command
itself refuses a CPU); the sound program must come out correct, and each
fault that a one-chip serving cell can have, planted underneath the timed
path, and the lower-precision control must come out not correct.
"""
import copy
import json
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.adapters.qwen2 import Shapes, model_config, to_program_params
from chipbench.harness import check, cli, common, program
from chipbench.reference import qwen2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "chipbench", "configs", "qd1_5b.json")) as f:
    SMOKE_CONFIG = dict(json.load(f), hidden_size=64, intermediate_size=128,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, vocab_size=300, head_dim=16)
with open(os.path.join(ROOT, "chipbench", "traffic", "rollout.longcot.json")) as f:
    SMOKE_TRAFFIC = dict(
        json.load(f), prompt_len=24, queue_depth=8, backlog_groups=16,
        output={"mean": 20, "cv": 0.6, "min": 4, "max": 40},
        engine={"page_size": 8, "num_pages": 60, "prefill_chunk": 32},
        # between the sound smoke runs' readings (at most ~0.05) and the
        # faults' and the control's (see the tests below)
        check={"requests": 12,
               "limits": {"logp_err_max": 0.2}})
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def _run(seed=2 ** 33 + 5, seconds=0.5, control=False, trace=False,
         tmp="/tmp", config=SMOKE_CONFIG):
    from chipbench.drivers import rollout
    run = common.Run(name="smoke", config=config,
                     traffic=copy.deepcopy(SMOKE_TRAFFIC), seed=seed,
                     seconds=seconds, trace=trace, t0=time.perf_counter(),
                     out_dir=os.path.join(tmp, "chipbench"),
                     clock=common.CompileClock(), peaks=PEAKS, control=control)
    return rollout.run(run)


def test_reference_matches_program_forward_in_float32():
    from repro.models.api import get_model
    cfg = dict(SMOKE_CONFIG, torch_dtype="float32")
    mcfg = model_config(cfg).replace(remat=False)
    w = qwen2.init_weights(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    params = to_program_params(w, mcfg)
    program.check_layout(params, mcfg)
    toks = np.random.default_rng(0).integers(0, 300, size=37)
    with jax.default_matmul_precision("highest"):
        want = get_model(mcfg).forward(params, mcfg, jnp.asarray(toks)[None])
    got = qwen2.logits_at(w, cfg, toks.tolist(), np.arange(37))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[0, :, :300]), atol=2e-4)


def test_driver_end_to_end_sound_run_is_correct(tmp_path):
    res = _run(tmp=str(tmp_path))
    assert res["correct"], res["checks"]
    e2e = res["end_to_end"]
    assert e2e["rollout_tokens_per_s"] > 0 and e2e["setup_s"] > 0
    assert e2e["token_gap_p95_ms"] > 0
    assert res["counts"]["checked_tokens"] > 0
    assert res["counts"]["compile_events_in_window"] == 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "rollout.longcot.qd1_5b"
    line = cli.result_line(res, cli.metrics_for(bench, cell, True),
                           {"platform": "cpu"}, True)
    # untraced: the counters' metrics are there, the trace's are not
    assert {"hbm_share.rollout", "mfu.rollout",
            "slot_occupancy.rollout"} <= set(line["metrics"])
    assert "idle_share.rollout" not in line["metrics"]
    assert list(line)[-1] == "checks"
    line = cli.result_line(res, cli.metrics_for(bench, cell, False),
                           {"platform": "cpu"}, False)
    assert set(line["metrics"]) == {"rollout_tokens_per_s",
                                    "token_gap_p95_ms", "setup_s"}


def _snapshots(monkeypatch):
    """Every request's prompt length, prefill progress and tokens, as the
    driver's ``Progress`` finds them at the window's start and after each
    step."""
    from chipbench.drivers import rollout
    seen = []

    def snap(engine):
        return {r.idx: (len(r.prompt), r.prefill_done, len(r.tokens))
                for r in list(program.active(engine))
                + program.finished(engine)}

    for name in ("start", "step"):
        def wrapped(self, engine, t, _orig=getattr(rollout.Progress, name)):
            _orig(self, engine, t)
            seen.append(snap(engine))
        monkeypatch.setattr(rollout.Progress, name, wrapped)
    return seen


def test_hbm_bytes_unchanged_by_per_call_weights(monkeypatch, tmp_path):
    """The window's bytes as they were counted when every jitted call read
    one constant of weight bytes: the weights once per decode step and per
    prefill chunk, the KV a chunk reads and writes up to its end and the KV
    a decode token reads up to its position, tallied here from the
    requests' states step by step."""
    seen = _snapshots(monkeypatch)
    res = _run(tmp=str(tmp_path))
    layer, counts = res["layer"], res["counts"]
    shapes = Shapes(SMOKE_CONFIG)
    assert counts["decode_steps"] > 0 and counts["prefill_calls"] > 0
    assert layer["hbm_weight_bytes"] == (
        (counts["decode_steps"] + counts["prefill_calls"])
        * shapes.weight_bytes_per_call(1))
    kv, last = 0, {i: (pf, n) for i, (_, pf, n) in seen[0].items()}
    for state in seen[1:]:
        for i, (plen, pf, n) in state.items():
            a, m = last.get(i, (0, 0))
            if pf < a:                       # preempted and re-admitted
                a = 0
            if pf > a:
                kv += pf
            if n < m:
                m = 0
            if n > m >= 1:
                kv += plen + m
            last[i] = (pf, n)
    assert layer["hbm_kv_bytes"] == kv * shapes.kv_bytes_per_token
    assert layer["hbm_bytes"] == layer["hbm_kv_bytes"] + layer["hbm_weight_bytes"]


def test_second_architecture_by_files_alone(monkeypatch, tmp_path):
    """An architecture the harness has never named, registered as its two
    modules (here wrappers of Qwen2's that record each call), runs through
    the unchanged driver and comes out correct."""
    from chipbench.adapters import qwen2 as qwen2_adapter
    calls = []

    def recorded(name, fn):
        def call(*args, **kwargs):
            calls.append(name)
            return fn(*args, **kwargs)
        return call

    class ToyShapes(qwen2_adapter.Shapes):
        def __init__(self, config):
            calls.append("Shapes")
            super().__init__(config)

        def weight_bytes_per_call(self, rows):
            calls.append("weight_bytes_per_call")
            return super().weight_bytes_per_call(rows)

    adapter = types.ModuleType("chipbench.adapters.toyarch")
    adapter.model_config = recorded("model_config", qwen2_adapter.model_config)
    adapter.to_program_params = recorded("to_program_params",
                                         qwen2_adapter.to_program_params)
    adapter.Shapes = ToyShapes
    reference = types.ModuleType("chipbench.reference.toyarch")
    reference.init_weights = recorded("init_weights", qwen2.init_weights)
    reference.logits_at = recorded("logits_at", qwen2.logits_at)
    monkeypatch.setitem(sys.modules, adapter.__name__, adapter)
    monkeypatch.setitem(sys.modules, reference.__name__, reference)
    res = _run(tmp=str(tmp_path),
               config=dict(SMOKE_CONFIG, architecture="toyarch"))
    assert res["correct"], res["checks"]
    assert {"model_config", "to_program_params", "Shapes",
            "weight_bytes_per_call", "init_weights", "logits_at"} <= set(calls)


def _alter_token(monkeypatch):
    from repro.serve import engine as eng
    orig = eng.PagedEngine._sample

    def altered(self, logits, key):
        tok, logp = orig(self, logits, key)
        return (tok + 1) % self.cfg.vocab, logp
    monkeypatch.setattr(eng.PagedEngine, "_sample", altered)


def _state_unchanged(monkeypatch):
    from repro.serve import engine as eng
    orig = eng.paged_decode_step

    def unchanged(p, cfg, kp, vp, bt, tok, pos, act):
        logits, _, _ = orig(p, cfg, kp, vp, bt, tok, pos, act)
        return logits, kp, vp
    monkeypatch.setattr(eng, "paged_decode_step", unchanged)


def _half_batch(monkeypatch):
    from repro.serve import engine as eng
    orig = eng.paged_decode_step

    def half(p, cfg, kp, vp, bt, tok, pos, act):
        keep = jnp.arange(act.shape[0]) < act.shape[0] // 2
        return orig(p, cfg, kp, vp, bt, tok, pos, jnp.where(keep, act, 0))
    monkeypatch.setattr(eng, "paged_decode_step", half)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
def test_fault_under_timed_path_is_not_correct(fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    res = _run(tmp=str(tmp_path))
    assert not res["correct"], res["checks"]


def test_control_is_not_correct_and_sound_run_is(tmp_path):
    res = _run(control=True, tmp=str(tmp_path))
    limits = SMOKE_TRAFFIC["check"]["limits"]
    sound = {k: v for k, (v, _) in res["checks"].items()}
    assert check.verdict(sound, limits)
    assert not check.verdict(res["control"], limits), res["control"]
