"""Gradient compression (subprocess, multi-device) + roofline parsing."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

COMPRESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    from repro.parallel.compression import (init_residual,
                                            make_compressed_allreduce)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    f = make_compressed_allreduce(mesh, "data")
    key = jax.random.PRNGKey(0)
    grads = {"w": jax.random.normal(key, (32, 32))}
    res = init_residual(grads)
    mean, res2 = f(grads, res)
    # every shard holds identical grads (replicated) → mean == grads
    err = float(jnp.max(jnp.abs(mean["w"] - grads["w"])))
    scale = float(jnp.max(jnp.abs(grads["w"]))) / 127.0
    # residual carries the quantization error exactly
    rec = float(jnp.max(jnp.abs(res2["w"] + mean["w"] - grads["w"])))
    print(json.dumps(dict(err=err, bound=scale, rec=rec)))
""")


def test_compressed_allreduce_bounded_error():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", COMPRESS_TEST],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] <= res["bound"] * 0.75 + 1e-6
    assert res["rec"] <= res["bound"] * 0.75 + 1e-6


# ------------------------------------------------------- roofline parsing
def test_parse_collectives_counts_and_wire_bytes():
    from repro.launch.roofline import parse_collectives
    hlo = """
      %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}
      %ag.1 = bf16[64]{0} all-gather(%y), replica_groups={{0,1}}
      %rs = f32[32]{0} reduce-scatter(%z), replica_groups={{0,1,2,3}}
      %done = f32[8]{0} all-reduce-done(%h)
      %cp = (s32[4]{0}, s32[4]{0}) collective-permute(%a, %b)
    """
    st = parse_collectives(hlo)
    assert st.counts["all-reduce"] == 1          # -done not double counted
    assert st.counts["all-gather"] == 1
    assert st.counts["reduce-scatter"] == 1
    assert st.counts["collective-permute"] == 1
    ar_bytes = 128 * 256 * 4
    assert st.result_bytes["all-reduce"] == ar_bytes
    assert st.wire_bytes["all-reduce"] == pytest.approx(
        2 * 3 / 4 * ar_bytes)
    assert st.wire_bytes["reduce-scatter"] == pytest.approx(3 * 32 * 4)


def test_loop_flop_correction_families():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.roofline import loop_flop_correction
    # full attention, 4k train: kv chunks = 4 → correction > 0
    c = loop_flop_correction(get_config("yi-34b"), SHAPES["train_4k"])
    assert c > 0
    # decode lowers UNCHUNKED (single-token fast path) → no correction
    assert loop_flop_correction(get_config("yi-34b"),
                                SHAPES["decode_32k"]) == 0.0
    # xlstm decode: single recurrent step, no loop → zero
    assert loop_flop_correction(get_config("xlstm-1.3b"),
                                SHAPES["decode_32k"]) == 0.0


def test_model_flops_for_cell_scaling():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.roofline import model_flops_for_cell
    cfg = get_config("qwen2.5-3b")
    tr = model_flops_for_cell(cfg, SHAPES["train_4k"])
    pf = model_flops_for_cell(cfg, SHAPES["prefill_32k"])
    de = model_flops_for_cell(cfg, SHAPES["decode_32k"])
    assert tr > pf > de
    # train = 6·N·D with D = 256·4096
    n_act = cfg.spec.params(active_only=True)
    assert tr == pytest.approx(6 * n_act * 256 * 4096)
