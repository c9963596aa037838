import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

The lines above MUST stay the very first statements — jax locks the
device count at first init, and this module needs 512 placeholder host
devices to build the production meshes.  Never set that flag globally.
It is pinned to the CPU so that on a machine with an accelerator neither
it nor its ``--all`` children (which inherit the environment) take the
chip.

Per cell this:
  1. builds the full-size ModelConfig,
  2. builds ShapeDtypeStruct stand-ins for params / optimizer / cache / batch
     (no allocation anywhere),
  3. jit-lowers the program with explicit in/out shardings
     (train_step for train_4k, prefill for prefill_32k,
      serve_step for decode_32k / long_500k),
  4. compiles, prints memory_analysis / cost_analysis,
  5. extracts the three roofline terms (+ collective inventory) and writes
     experiments/dryrun/<arch>__<shape>__<mesh>.json.

Driver mode (--all) runs each cell in a fresh subprocess (XLA state isolation
+ resumability: existing JSONs are skipped unless --force).
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from functools import partial
from pathlib import Path

from repro.obs import log

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _cell_path(arch: str, shape: str, mesh_name: str, tag: str = "") -> Path:
    safe = arch.replace("/", "_")
    sfx = f"__{tag}" if tag else ""
    return RESULTS_DIR / f"{safe}__{shape}__{mesh_name}{sfx}.json"


# --------------------------------------------------------------- one cell
def run_cell(arch: str, shape_name: str, mesh_name: str,
             save: bool = True, overrides: dict | None = None,
             tag: str = "") -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.shapes import SHAPES, applicable_shapes
    from repro.models.api import get_model, train_input_specs
    from repro.optim.adamw import AdamWConfig
    from repro.parallel import sharding as shd
    from repro.rl.grpo import make_train_step, make_serve_step, make_prefill
    from repro.launch.mesh import make_production_mesh
    from repro.launch import roofline as rf

    base_cfg = get_config(arch)
    if overrides:
        base_cfg = base_cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    cfg = base_cfg
    if shape not in applicable_shapes(cfg):
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "skipped",
                  "reason": "long_500k needs sub-quadratic attention "
                            "(full-attention arch; see DESIGN.md)"}
        if save and not tag:
            RESULTS_DIR.mkdir(parents=True, exist_ok=True)
            _cell_path(arch, shape_name, mesh_name).write_text(
                json.dumps(result, indent=2))
        return result

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_dev = mesh.size
    t0 = time.time()

    def lower_program(cfg):
        model = get_model(cfg)
        params_shape = jax.eval_shape(lambda k: model.init(k, cfg),
                                      jax.random.PRNGKey(0))
        # serving (prefill/decode): weights are read-only → fully shard
        # over data axes too when the model-axis shard alone exceeds the
        # HBM budget (stationary weights, all-gathered per layer);
        # small/mid models keep TP-only weights (no per-step gathers).
        import numpy as _np
        msize = mesh.shape.get("model", 1) if hasattr(mesh.shape, "get") \
            else dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        per_dev = sum(_np.prod(l.shape) * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves(params_shape)
                      ) / msize
        fsdp = cfg.fsdp_params or (shape.kind != "train"
                                   and per_dev > 8e9)
        p_specs = shd.param_pspecs(params_shape, cfg, mesh, fsdp=fsdp)
        p_sh = shd.named(p_specs, mesh)
        params_sds = jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sp),
            params_shape, p_sh)

        if shape.kind == "train":
            from repro.optim.adamw import adamw_init
            opt_shape = jax.eval_shape(partial(adamw_init), params_shape)
            o_specs = {
                "m": shd.opt_state_pspecs(params_shape, cfg, mesh),
                "v": shd.opt_state_pspecs(params_shape, cfg, mesh),
                "count": P(),
            }
            o_sh = shd.named(o_specs, mesh)
            opt_sds = jax.tree_util.tree_map(
                lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sp),
                opt_shape, o_sh)
            b_specs_sds = train_input_specs(
                cfg, batch=shape.global_batch, seq_len=shape.seq_len)
            b_specs = shd.batch_pspecs(b_specs_sds, mesh,
                                       include_model=(cfg.shard_mode
                                                      == "dp"))
            b_sh = {k: NamedSharding(mesh, v) for k, v in b_specs.items()}
            batch_sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                 sharding=b_sh[k])
                         for k, v in b_specs_sds.items()}
            step = make_train_step(cfg)
            jitted = jax.jit(step, donate_argnums=(0, 1),
                             out_shardings=(p_sh, o_sh, None))
            return jitted.lower(params_sds, opt_sds, batch_sds)

        elif shape.kind == "prefill":
            tokens = jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len), jnp.int32,
                sharding=NamedSharding(
                    mesh, shd.batch_pspecs(
                        {"t": jax.ShapeDtypeStruct(
                            (shape.global_batch, shape.seq_len),
                            jnp.int32)}, mesh)["t"]))
            extras = {}
            if cfg.family == "encdec":
                extras["frames"] = jax.ShapeDtypeStruct(
                    (shape.global_batch, cfg.encoder_seq, cfg.enc_dim),
                    cfg.jdtype, sharding=NamedSharding(
                        mesh, P(tuple(a for a in ("pod", "data")
                                      if a in mesh.axis_names), None, None)))
            if cfg.family == "vlm":
                extras["patches"] = jax.ShapeDtypeStruct(
                    (shape.global_batch, cfg.encoder_seq, cfg.enc_dim),
                    cfg.jdtype, sharding=NamedSharding(
                        mesh, P(tuple(a for a in ("pod", "data")
                                      if a in mesh.axis_names), None, None)))
            fn = make_prefill(cfg, max_len=shape.seq_len)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(cfg, batch=shape.global_batch,
                                         max_len=shape.seq_len))
            c_sh = shd.named(shd.cache_pspecs(cache_shape, cfg, mesh), mesh)
            jitted = jax.jit(fn, out_shardings=(None, c_sh))
            return jitted.lower(params_sds, tokens, **extras)

        else:  # decode
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(cfg, batch=shape.global_batch,
                                         max_len=shape.seq_len))
            c_specs = shd.cache_pspecs(cache_shape, cfg, mesh)
            c_sh = shd.named(c_specs, mesh)
            cache_sds = jax.tree_util.tree_map(
                lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sp),
                cache_shape, c_sh)
            bdim = shd.batch_pspecs(
                {"t": jax.ShapeDtypeStruct((shape.global_batch,),
                                           jnp.int32)}, mesh)["t"]
            tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32,
                                       sharding=NamedSharding(mesh, bdim))
            pos = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32,
                                       sharding=NamedSharding(mesh, bdim))
            step = make_serve_step(cfg)
            jitted = jax.jit(step, donate_argnums=(1,),
                             out_shardings=(None, c_sh))
            return jitted.lower(params_sds, cache_sds, tok, pos)

    # DUAL LOWERING.  (a) scanned layers at FULL depth: realistic buffer
    # reuse → memory analysis.  (b) counting modules with layers UNROLLED:
    # XLA cost analysis counts while bodies once, so flops / collective
    # inventory need unrolled layers; for deep models we compile two
    # reduced-depth unrolled variants (L=4 and L=8 — layers are
    # homogeneous) and linearly extrapolate the per-layer deltas to full
    # depth (validated against a full unroll on danube-24L: <1% error).
    # Chunked sequence loops remain loops and are corrected analytically.
    def reduced(cfg, L):
        kw = dict(n_layers=L, unroll_layers=True)
        if cfg.n_encoder_layers:
            kw["n_encoder_layers"] = max(
                1, round(cfg.n_encoder_layers * L / cfg.n_layers))
        return cfg.replace(**kw)

    with mesh:
        lowered_scan = lower_program(base_cfg)
        compiled_scan = lowered_scan.compile()
        t_scan = time.time() - t0

        t1 = time.time()
        L = base_cfg.n_layers
        if L <= 12:
            lowered = lower_program(base_cfg.replace(unroll_layers=True))
            compiled = lowered.compile()
            extrapolate = None
        else:
            lo4 = lower_program(reduced(base_cfg, 4))
            c4 = lo4.compile()
            lowered = lower_program(reduced(base_cfg, 8))
            compiled = lowered.compile()
            extrapolate = (c4, 4, 8, L)
        t_lower = 0.0
        t_compile = time.time() - t1
    cfg = base_cfg

    mem = None
    mem_per_dev = None
    try:
        ma = compiled_scan.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
        }
        if mem["argument_bytes"] is not None:
            mem_per_dev = (mem["argument_bytes"] + mem["temp_bytes"]
                           + mem["output_bytes"]
                           - (mem["alias_bytes"] or 0))
        log.info(f"memory_analysis: {mem}", memory_analysis=mem)
    except Exception as e:                                 # pragma: no cover
        log.info(f"memory_analysis unavailable: {e}", error=str(e))

    def _cost_of(comp):
        try:
            ca = comp.cost_analysis()
            return dict(ca) if ca else {}
        except Exception as e:                             # pragma: no cover
            log.info(f"cost_analysis unavailable: {e}", error=str(e))
            return {}

    def _hlo_of(comp, low):
        try:
            return comp.as_text()
        except Exception:
            return low.as_text()

    cost = _cost_of(compiled)
    hlo = _hlo_of(compiled, lowered)
    coll_override = None
    if extrapolate is not None:
        from repro.launch.roofline import parse_collectives
        c4, L1, L2, L = extrapolate
        cost4 = _cost_of(c4)
        scale = (L - L2) / (L2 - L1)
        for key in ("flops", "bytes accessed"):
            hi = float(cost.get(key, 0.0))
            lo = float(cost4.get(key, 0.0))
            cost[key] = hi + (hi - lo) * scale
        st_hi = parse_collectives(hlo)
        st_lo = parse_collectives(_hlo_of(c4, lo4))
        coll_override = {
            "counts": {k: int(round(st_hi.counts.get(k, 0)
                       + (st_hi.counts.get(k, 0)
                          - st_lo.counts.get(k, 0)) * scale))
                       for k in set(st_hi.counts) | set(st_lo.counts)},
            "wire_bytes": {k: st_hi.wire_bytes.get(k, 0.0)
                           + (st_hi.wire_bytes.get(k, 0.0)
                              - st_lo.wire_bytes.get(k, 0.0)) * scale
                           for k in set(st_hi.wire_bytes)
                           | set(st_lo.wire_bytes)},
        }
    log.info("cost_analysis: flops=%.3e bytes=%.3e%s" %
             (cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
              " (extrapolated)" if extrapolate else ""),
             flops=cost.get("flops", 0.0),
             bytes_accessed=cost.get("bytes accessed", 0.0),
             extrapolated=extrapolate is not None)

    calib = rf.calibrate_cost_analysis()
    roof = rf.build_roofline(
        arch=arch, shape=shape_name, mesh_name=mesh_name, n_devices=n_dev,
        cost=cost, hlo_text=hlo,
        model_flops=rf.model_flops_for_cell(cfg, shape),
        # memory_analysis reports per-partition (per-device) sizes
        mem_per_dev_bytes=mem_per_dev,
        calib_factor=calib,
        mix_correction_flops=rf.loop_flop_correction(cfg, shape),
        collectives_override=coll_override)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "n_devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "scan_compile_s": round(t_scan, 2),
        "memory_analysis": mem, "cost_analysis": {
            k: cost[k] for k in ("flops", "bytes accessed")
            if k in cost},
        "calibration_factor": calib,
        "roofline": roof.to_json(),
    }
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        if tag:
            result["overrides"] = {k: str(v)
                                   for k, v in (overrides or {}).items()}
        _cell_path(arch, shape_name, mesh_name, tag).write_text(
            json.dumps(result, indent=2))
    summary = {k: result[k] for k in
               ("arch", "shape", "mesh", "status", "lower_s", "compile_s")}
    log.info(json.dumps(summary), **summary)
    log.info("roofline: compute=%.4fs memory=%.4fs collective=%.4fs -> %s" %
             (roof.t_compute, roof.t_memory, roof.t_collective,
              roof.bottleneck),
             t_compute=roof.t_compute, t_memory=roof.t_memory,
             t_collective=roof.t_collective, bottleneck=roof.bottleneck)
    return result


# ------------------------------------------------------------------ driver
def run_all(meshes, archs=None, shapes=None, force=False,
            timeout: int = 3600) -> None:
    from repro.configs import ASSIGNED_ARCHS
    from repro.configs.shapes import SHAPES
    archs = archs or ASSIGNED_ARCHS
    shapes = shapes or list(SHAPES)
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                out = _cell_path(arch, shape, mesh_name)
                if out.exists() and not force:
                    prev = json.loads(out.read_text())
                    if prev.get("status") in ("ok", "skipped"):
                        continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", mesh_name]
                log.info(f"\n=== {arch} × {shape} × {mesh_name} ===",
                         arch=arch, shape=shape, mesh=mesh_name)
                try:
                    r = subprocess.run(cmd, timeout=timeout)
                    if r.returncode != 0:
                        failures.append((arch, shape, mesh_name,
                                         f"exit {r.returncode}"))
                except subprocess.TimeoutExpired:
                    failures.append((arch, shape, mesh_name, "timeout"))
    if failures:
        log.info("\nFAILURES:", failures=failures)
        for f in failures:
            log.info(f"   {f}")
        sys.exit(1)
    log.info("\nall requested dry-run cells green")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (hillclimb knobs)")
    ap.add_argument("--tag", default="", help="suffix for the result JSON")
    ap.add_argument("--timeout", type=int, default=3600)
    log.add_flags(ap)
    args = ap.parse_args()
    log.configure(args)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        archs = [args.arch] if args.arch else None
        shapes = [args.shape] if args.shape else None
        run_all(meshes, archs=archs, shapes=shapes, force=args.force,
                timeout=args.timeout)
        return
    assert args.arch and args.shape, "--arch and --shape required"
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = (v if not v.replace("-", "").isdigit() else int(v))
        if v in ("True", "False"):
            overrides[k] = v == "True"
    for m in meshes:
        res = run_cell(args.arch, args.shape, m, overrides=overrides or None,
                       tag=args.tag)
        if res.get("status") not in ("ok", "skipped"):
            sys.exit(1)


if __name__ == "__main__":
    main()
