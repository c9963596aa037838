"""Sharding rules + a miniature dry-run in a subprocess (8 fake devices).

The subprocess is required because jax locks the host device count at first
init — the main test process must keep seeing 1 device.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_smoke_config
from repro.models.api import get_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_cover_every_leaf(arch):
    """Every parameter gets a spec of matching rank; model-axis entries only
    on dims that exist."""
    from repro.parallel import sharding as shd
    from repro.launch.mesh import make_mesh
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k, cfg),
                            jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = shd.param_pspecs(shapes, cfg, mesh)
    leaves_s, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    leaves_p = jax.tree_util.tree_leaves(shapes)
    assert len(leaves_s) == len(leaves_p)
    for spec, leaf in zip(leaves_s, leaves_p):
        assert isinstance(spec, P)
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)


def test_zero_extend_picks_divisible_dim():
    from repro.parallel.sharding import zero_extend
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    # data axis size 1 → everything divides; largest unsharded dim chosen
    spec = zero_extend(P(None, "model"), (64, 128), mesh)
    assert spec[0] == ("data",) or spec[0] == "data" or spec == \
        P(("data",), "model") or spec == P("data", "model")


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.models.api import get_model, train_input_specs
    from repro.optim.adamw import adamw_init
    from repro.parallel import sharding as shd
    from repro.rl.grpo import make_train_step
    from repro.launch.roofline import parse_collectives
    from repro.launch.mesh import make_mesh

    cfg = get_smoke_config("{arch}").replace(dtype="float32")
    mesh = make_mesh((2, 4), ("data", "model"))
    model = get_model(cfg)
    params_shape = jax.eval_shape(lambda k: model.init(k, cfg),
                                  jax.random.PRNGKey(0))
    p_sh = shd.named(shd.param_pspecs(params_shape, cfg, mesh), mesh)
    params_sds = jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sp),
        params_shape, p_sh)
    opt_shape = jax.eval_shape(partial(adamw_init), params_shape)
    o_specs = dict(m=shd.opt_state_pspecs(params_shape, cfg, mesh),
                   v=shd.opt_state_pspecs(params_shape, cfg, mesh),
                   count=P())
    o_sh = shd.named(o_specs, mesh)
    opt_sds = jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sp),
        opt_shape, o_sh)
    bs = train_input_specs(cfg, batch=4, seq_len=32)
    bsp = shd.batch_pspecs(bs, mesh)
    batch_sds = {{k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, bsp[k]))
        for k, v in bs.items()}}
    with mesh:
        step = make_train_step(cfg)
        lowered = jax.jit(step, donate_argnums=(0, 1),
                          out_shardings=(p_sh, o_sh, None)).lower(
            params_sds, opt_sds, batch_sds)
        compiled = lowered.compile()
    stats = parse_collectives(compiled.as_text())
    ca = compiled.cost_analysis() or dict()
    print(json.dumps(dict(ok=True,
                          collectives=sum(stats.counts.values()),
                          flops=float(ca.get("flops", 0)))))
""")


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen3-moe-235b-a22b",
                                  "xlstm-1.3b", "hymba-1.5b",
                                  "whisper-small"])
def test_mini_dryrun_compiles_and_has_collectives(arch):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c",
                          MINI_DRYRUN.format(arch=arch)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"]
    assert res["collectives"] > 0        # TP really sharded something
