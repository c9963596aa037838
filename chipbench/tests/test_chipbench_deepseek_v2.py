"""The DeepSeek-V2 configuration, its adapter and its reference, at smoke
size on the CPU: the published counts, the lookup of both modules, the
reference against the program's forward, and a shrunken copy of the
configuration through the unchanged rollout driver, sound and controlled.
"""
import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.adapters import deepseek_v2 as adapter
from chipbench.harness import check, cli, common, program
from chipbench.reference import deepseek_v2 as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "rollout.longcot.dsv2lite_ep8"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("chipbench", "configs", "dsv2lite_ep8.json")
# every kind of layer and every mechanism, at toy widths: one dense layer,
# two expert layers, 2 of 16 experts held from id 4, top-4 of 16, YaRN
SMOKE_CONFIG = dict(
    CONFIG, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    vocab_size=300, n_routed_experts_published=16, n_routed_experts=2,
    first_held_expert=4, num_experts_per_tok=4,
    rope_scaling=dict(CONFIG["rope_scaling"],
                      original_max_position_embeddings=64))
SMOKE_TRAFFIC = dict(
    _json("chipbench", "traffic", "rollout.longcot.dsv2lite.json"),
    prompt_len=24, queue_depth=8, backlog_groups=16,
    output={"mean": 20, "cv": 0.6, "min": 4, "max": 40},
    engine={"page_size": 8, "num_pages": 60, "prefill_chunk": 32},
    # between the sound smoke runs' readings in float32 (at most 8.6e-6 over
    # 24 runs of 4 seeds and 0.2-1 s windows) and the control's (at least
    # 0.55), over 100x from each
    check={"requests": 12, "limits": {"logp_err_max": 1e-3}})
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def test_published_counts():
    shapes = adapter.Shapes(CONFIG)
    assert shapes.params_holding(64) == 15_706_484_224
    assert shapes.params == 3_110_989_312
    assert shapes.kv_bytes_per_token == 31_104
    assert shapes.vocab == 102_400
    assert shapes.expert_params * 2 == 17_301_504
    # the absorbed form: 2 * 16 * (576 + 512) operations a key a layer
    assert shapes.token_flops(101) - shapes.token_flops(100) == \
        27 * 2 * 16 * (576 + 512)
    # the weights outside the experts, and 8 * (1 - (58/64)^rows) held
    # experts a layer
    fixed = (shapes.params_holding(0) - 102_400 * 2048) * 2
    for rows in (1, 10, 2048):
        reached = 8 * (1 - (58 / 64) ** rows)
        assert shapes.weight_bytes_per_call(rows) == round(
            fixed + 26 * reached * 17_301_504)


def test_span_flops_adds_up_over_chunks():
    shapes = adapter.Shapes(CONFIG)
    assert shapes.span_flops(0, 5000) == pytest.approx(
        shapes.span_flops(0, 2048) + shapes.span_flops(2048, 2952), rel=1e-12)


def test_cell_finds_both_modules_and_the_programs_config():
    from repro.configs.deepseek_v2_lite import CONFIG as program_config
    found = cli.load_cell(CELL)
    assert program.adapter(found["config"]) is adapter
    assert program.reference(found["config"]) is reference
    mcfg = adapter.model_config(found["config"])
    # the published model, holding experts 0-7 of 64
    assert mcfg == program_config.replace(
        name="dsv2lite_ep8", dtype="bfloat16", experts_held=8,
        expert_offset=0)
    assert found["cell"]["chips"] == 1


def test_reference_matches_program_forward_in_float32(monkeypatch):
    from repro.models import moe
    from repro.models.api import get_model
    cfg = dict(SMOKE_CONFIG, torch_dtype="float32")
    mcfg = adapter.model_config(cfg).replace(remat=False)
    w = reference.init_weights(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    params = adapter.to_program_params(w, mcfg)
    program.check_layout(params, mcfg)
    toks = np.random.default_rng(0).integers(0, 300, size=300)
    monkeypatch.setattr(moe, "CAPACITY_FACTOR", 100.0)     # drops nothing
    with jax.default_matmul_precision("highest"):
        want = get_model(mcfg).forward(params, mcfg, jnp.asarray(toks)[None])
    got = reference.logits_at(w, cfg, toks.tolist(), np.arange(300))
    # float32 both, sums in other orders: measured 8e-6 on logits ~5
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[0, :, :300]), atol=2e-4)


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    from chipbench.drivers import rollout
    # float32, weights too: in bfloat16 at these widths a routing near-tie
    # (4th and 5th gate within ~0.6 %, the 5th a held expert) flips which
    # experts a token uses, and whether the checked sample holds such a
    # token depends on how many steps the wall-clock window fits (measured:
    # 0.30 on one such token, 0.04-0.08 otherwise).  The chip cell's limit
    # allows for that; here the driver's whole path is held to float32.
    config = dict(SMOKE_CONFIG, torch_dtype="float32")
    run = common.Run(name="smoke", config=config,
                     traffic=copy.deepcopy(SMOKE_TRAFFIC), seed=2 ** 33 + 5,
                     seconds=0.5, trace=False, t0=time.perf_counter(),
                     out_dir=str(tmp_path_factory.mktemp("chipbench")),
                     clock=common.CompileClock(), peaks=PEAKS, control=True)
    return rollout.run(run)


def test_shrunken_configuration_runs_the_driver_correct(smoke_run):
    res = smoke_run
    assert res["correct"], res["checks"]
    assert res["counts"]["checked_tokens"] > 0
    assert res["counts"]["compile_events_in_window"] == 0
    assert res["end_to_end"]["rollout_tokens_per_s"] > 0
    bench = _json("BENCHMARK.json")
    line = cli.result_line(res, cli.metrics_for(bench, CELL, True),
                           {"platform": "cpu"}, True)
    # untraced: the counters' metrics are there, the trace's are not
    assert {"hbm_share.dsv2lite", "mfu.dsv2lite",
            "slot_occupancy.dsv2lite"} == set(line["metrics"])


def test_fp8_control_fails_the_check(smoke_run):
    limits = SMOKE_TRAFFIC["check"]["limits"]
    assert check.verdict({k: v for k, (v, _) in smoke_run["checks"].items()},
                         limits)
    assert not check.verdict(smoke_run["control"], limits), smoke_run["control"]


def test_scope_split_attributes_device_ops_to_the_named_scopes():
    """The decode program's compiled text carries each of the five scopes;
    device operations go to their instruction's scope, loops are left out,
    and operations of other programs are not counted."""
    from repro.configs import get_smoke_config
    from repro.models.api import get_model
    from repro.serve.kv_cache import PagedKVCache
    from repro.serve.model import paged_decode_step
    from chipbench.tools import scope_split
    cfg = get_smoke_config("deepseek-v2-lite")
    params = jax.eval_shape(lambda: get_model(cfg).init(jax.random.PRNGKey(0),
                                                        cfg))
    kv = PagedKVCache(cfg, max_slots=3, max_len=40, page_size=8)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = jax.jit(paged_decode_step, static_argnums=1).lower(
        params, cfg, kv.k_pages, kv.v_pages, i32(3, kv.maxp), i32(3), i32(3),
        i32(3)).compile().as_text()
    scopes = scope_split.instruction_scopes(text)
    assert set(scope_split.SCOPES) <= set(scopes.values())
    named = {s: next(n for n, v in scopes.items() if v == s)
             for s in scope_split.SCOPES}
    plain = next(n for n, v in scopes.items() if not v)
    prog = "jit_paged_decode_step"
    ops = [(prog, f"%{named['mla.attend']} = f32[2] fusion()", 2.0),
           (prog, f"%{named['mla.attend']} = f32[2] fusion()", 1.0),
           (prog, f"%{named['moe.experts']} = f32[2] fusion()", 0.5),
           (prog, f"%{plain} = f32[2] copy()", 0.25),
           (prog, "%while.1 = (f32[2]) while(%t), body=%b", 9.0),
           ("jit_other", f"%{named['moe.route']} = f32[2] add()", 7.0)]
    assert scope_split.split(ops, {prog: scopes}) == {
        prog: {"mla.attend": 3.0, "moe.experts": 0.5, "other": 0.25}}
