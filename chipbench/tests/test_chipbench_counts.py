"""Counts from shapes, the peaks table, the traffic generator, the lookup of
an architecture's modules and the contract of BENCHMARK.json, on the CPU."""
import json
import os
import re
import shutil
import time

import numpy as np
import pytest

from chipbench.adapters.qwen2 import Shapes
from chipbench.harness import cli, common, program
from chipbench.harness.traffic import RolloutPlan, pages_for

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_qd1_5b_counts_by_hand():
    s = Shapes(_json("chipbench", "configs", "qd1_5b.json"))
    # per layer: q 1536x1536 + k, v 1536x256 + o 1536x1536, biases
    # 1536 + 2x256, two norms of 1536, MLP 3 x 1536 x 8960
    layer = (2 * 1536 * 1536 + 2 * 1536 * 256) + (1536 + 512) + 2 * 1536 \
        + 3 * 1536 * 8960
    assert s.layer_params == layer == 46_797_824
    assert s.params == 28 * layer + 2 * 151_936 * 1536 + 1536 == 1_777_088_000
    # K and V, 28 layers, 2 KV heads of 128, two bytes
    assert s.kv_bytes_per_token == 2 * 28 * 2 * 128 * 2 == 28_672
    assert s.matmul_params == 28 * (layer - 2048 - 3072) + 151_936 * 1536


def test_weight_bytes_per_call_reads_every_weight_but_the_lookup_table():
    s = Shapes(_json("chipbench", "configs", "qd1_5b.json"))
    want = (1_777_088_000 - 151_936 * 1536) * 2
    assert [s.weight_bytes_per_call(r) for r in (1, 13, 2048)] == [want] * 3


def test_adapter_lookup_gives_the_programs_qwen2_config():
    from repro.models.api import ModelConfig
    config = _json("chipbench", "configs", "qd1_5b.json")
    assert program.adapter(config).model_config(config) == ModelConfig(
        name="qd1_5b", family="dense", n_layers=28, d_model=1536,
        n_heads=12, n_kv_heads=2, head_dim=128, d_ff=8960, vocab=151_936,
        qkv_bias=True, tie_embeddings=False, rope_theta=10000.0,
        norm_eps=1e-6, dtype="bfloat16")


def test_span_flops_is_sum_of_token_flops():
    s = Shapes(_json("chipbench", "configs", "qd1_5b.json"))
    start, n = 700, 37
    total = sum(s.token_flops(start + k + 1) for k in range(n))
    assert s.span_flops(start, n) == pytest.approx(total, rel=1e-12)
    assert s.train_flops(64) == pytest.approx(3 * s.span_flops(0, 64))


def test_peaks_known_and_unknown_kinds():
    assert common.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert common.peaks("TPU v5e")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        common.peaks("cpu")


@pytest.mark.parametrize("traffic", ["rollout.longcot"])
def test_plan_sizes_fixed_order_from_seed(traffic):
    t = _json("chipbench", "traffic", traffic + ".json")
    a, b = RolloutPlan(t, 151_936, 1), RolloutPlan(t, 151_936, 2 ** 40 + 3)
    assert a.max_slots == b.max_slots
    ra, rb = a.inflight(), b.inflight()
    key = lambda r: (len(r.prompt), r.max_new)
    assert sorted(map(key, ra)) == sorted(map(key, rb))   # same work
    assert [r.prompt for r in ra] != [r.prompt for r in rb]
    need = sum(pages_for(len(r.prompt) + 1, a.page) + 1 for r in ra)
    assert need <= a.num_pages - 1                       # it fits the pool
    assert all(len(r.prompt) + r.max_new <= a.max_len for r in ra)
    group = next(a.backlog())
    assert len(group) == t["group_size"]
    assert len({tuple(r.prompt) for r in group}) == 1


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_contract():
    b = _json("BENCHMARK.json")
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        assert _json(c["file"])["name"] == c["name"]
        arch = _json(c["file"])["architecture"]
        for package in ("adapters", "reference"):
            assert os.path.exists(os.path.join(
                ROOT, "chipbench", package, arch + ".py"))
    cells = [w["name"] for w in b["workloads"]]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        t = _json("chipbench", "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "drivers", t["kind"] + ".py"))
    assert {c["config"] for c in b["workloads"]} == set(names)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", m["name"] + ".py"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for n in names + cells:
        assert NAME.match(n)


def test_command_refuses_a_cpu_and_prints_no_result():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "rollout.longcot.qd1_5b", "--seed", str(2 ** 40 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_unknown_architecture_is_refused_before_device_work(
        tmp_path, monkeypatch, capsys):
    """A copy of the benchmark whose configuration names an architecture
    with neither an adapter nor a reference: the command refuses it while
    it loads the cell, before it looks for a chip."""
    config = dict(_json("chipbench", "configs", "qd1_5b.json"),
                  architecture="nosuch")
    bench = tmp_path / "chipbench"
    (bench / "configs").mkdir(parents=True)
    (bench / "configs" / "qd1_5b.json").write_text(json.dumps(config))
    shutil.copytree(os.path.join(ROOT, "chipbench", "traffic"),
                    bench / "traffic")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(cli, "ROOT", str(tmp_path))
    monkeypatch.setattr(cli, "BENCH", str(bench))

    def no_device_work(chips):
        raise AssertionError("looked for a chip before the refusal")
    monkeypatch.setattr(cli, "accelerator", no_device_work)
    rc = cli.main(["--workload", "rollout.longcot.qd1_5b",
                   "--seed", str(2 ** 40 + 7), "--seconds", "1"],
                  time.perf_counter())
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "chipbench/adapters/nosuch.py" in err
    assert "chipbench/reference/nosuch.py" in err
