"""Timing harness for the Pallas kernel sweep.

Two measurement modes, chosen by what the process is running on:

* ``device`` — a real accelerator backend: every (shape × config) candidate
  is compiled and wall-clocked (best of ``DEVICE_REPEATS``, after warmup).
* ``interpret`` — CPU (the CI contract): per kernel, one *micro* shape is
  executed with ``interpret=True`` to validate the config plumbing, and a
  compiled micro cell's ``cost_analysis()`` calibrates the analytic FLOP
  model (the same calibration idiom as ``launch/dryrun.py`` — XLA may
  report per-partition or whole-program numbers, and counts loop bodies
  once, so the ratio is taken against whichever granularity it matches;
  see ``roofline.calibrate_cost_analysis``).  Candidate times are then
  roofline estimates: max(compute at alignment-degraded MXU utilization,
  HBM stream time) + per-grid-step overhead — a *model* of the device, but
  one that prices block-size effects (padding waste, k/v re-streaming,
  grid overheads, VMEM fit) far finer than the hand-calibrated per-phase
  MFU constants the scheduler used before.

Both modes produce the same ``Measurement``; CostDB records carry the mode
so merging prefers real device numbers over interpreter estimates.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from ..core.cluster import PROFILES, DeviceProfile
from .space import KernelSpace, ShapeBucket, SPACES

# Roofline-estimate priors (interpret mode only; device mode measures).
BASE_MXU_UTIL = 0.72       # pipelined MXU utilization at perfect alignment
STREAM_EFF = 0.80          # achievable fraction of peak HBM bandwidth
GRID_STEP_S = 0.03e-6      # per-grid-step sequencing overhead (amortized
                           # under double-buffered DMA; favors fewer tiles)
MXU_LANE = 128             # MXU consumes 128×128 tiles
DEVICE_REPEATS = 5

# Micro shapes: small enough for interpret-mode execution on CPU.
_MICRO_SHAPES = {
    "flash_attention": ShapeBucket.make("micro", B=1, S=256, H=2, D=128),
    "decode_attention": ShapeBucket.make("micro", B=4, C=256, H=4, Hkv=2,
                                         D=128),
    "paged_attention": ShapeBucket.make("micro", B=4, C=256, H=4, Hkv=2,
                                        D=128),
    "ssm_scan": ShapeBucket.make("micro", B=1, S=256, H=2, D=128),
}
_MICRO_CONFIGS = {
    "flash_attention": {"block_q": 64, "block_k": 64},
    "decode_attention": {"block_c": 128},
    "paged_attention": {"page_size": 128},
    "ssm_scan": {"chunk": 64},
}


@dataclass(frozen=True)
class Measurement:
    config: Dict[str, int]
    time_s: float
    flops: float               # executed, incl. padding waste
    useful_flops: float
    bytes: float
    mode: str                  # "device" | "interpret"


# ------------------------------------------------------------- kernel calls
def _kernel_fn(kernel: str, shape: ShapeBucket,
               cfg: Dict[str, int], interpret: bool) -> Tuple[Callable, tuple]:
    """(callable, example args) invoking the real ops.py entry point with
    the candidate config."""
    import jax
    import jax.numpy as jnp

    d = shape.d
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    if kernel == "flash_attention":
        from ..kernels.flash_attention.ops import flash_attention
        q = jax.random.normal(ks[0], (d["B"], d["S"], d["H"], d["D"]),
                              jnp.bfloat16)
        k = jax.random.normal(ks[1], q.shape, jnp.bfloat16)
        v = jax.random.normal(ks[2], q.shape, jnp.bfloat16)

        def fn(q, k, v):
            return flash_attention(q, k, v, True, None, None,
                                   cfg["block_q"], cfg["block_k"], interpret)
        return fn, (q, k, v)

    if kernel == "decode_attention":
        from ..kernels.decode_attention.ops import decode_attention
        q = jax.random.normal(ks[0], (d["B"], d["H"], d["D"]), jnp.bfloat16)
        k = jax.random.normal(ks[1], (d["B"], d["C"], d["Hkv"], d["D"]),
                              jnp.bfloat16)
        v = jax.random.normal(ks[2], k.shape, jnp.bfloat16)
        q_pos = jnp.full((d["B"],), d["C"] - 1, jnp.int32)
        k_pos = jnp.broadcast_to(jnp.arange(d["C"], dtype=jnp.int32),
                                 (d["B"], d["C"]))

        def fn(q, k, v, q_pos, k_pos):
            return decode_attention(q, k, v, q_pos, k_pos,
                                    block_c=cfg["block_c"],
                                    interpret=interpret)
        return fn, (q, k, v, q_pos, k_pos)

    if kernel == "paged_attention":
        from ..kernels.paged_attention.ops import paged_decode_attention
        pg = cfg["page_size"]
        pages = -(-d["C"] // pg)
        P = d["B"] * pages + 1                      # + the null page
        q = jax.random.normal(ks[0], (d["B"], d["H"], d["D"]), jnp.bfloat16)
        k = jax.random.normal(ks[1], (P, pg, d["Hkv"], d["D"]), jnp.bfloat16)
        v = jax.random.normal(ks[2], k.shape, jnp.bfloat16)
        # shuffled tables: the gather must price non-contiguous pages
        perm = jax.random.permutation(ks[3], jnp.arange(1, P, dtype=jnp.int32))
        bt = perm.reshape(d["B"], pages)
        lens = jnp.full((d["B"],), d["C"], jnp.int32)

        def fn(q, k, v, bt, lens):
            return paged_decode_attention(q, k, v, bt, lens,
                                          interpret=interpret)
        return fn, (q, k, v, bt, lens)

    if kernel == "ssm_scan":
        from ..kernels.ssm_scan.ops import mlstm_scan
        q = jax.random.normal(ks[0], (d["B"], d["S"], d["H"], d["D"]),
                              jnp.bfloat16)
        k = jax.random.normal(ks[1], q.shape, jnp.bfloat16)
        v = jax.random.normal(ks[2], q.shape, jnp.bfloat16)
        ig = jax.random.normal(ks[3], (d["B"], d["S"], d["H"]))
        fg = jax.random.normal(ks[4], (d["B"], d["S"], d["H"])) + 2.0

        def fn(q, k, v, ig, fg):
            return mlstm_scan(q, k, v, ig, fg, chunk=cfg["chunk"],
                              interpret=interpret)
        return fn, (q, k, v, ig, fg)

    raise KeyError(f"unknown kernel {kernel!r} (known: {sorted(SPACES)})")


def on_device_type() -> Optional[str]:
    """Profile name when running on a real accelerator, else None."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    from ..kernels import tuning
    return tuning.current_device_type()


# --------------------------------------------------------------- calibration
_CALIB: Dict[str, float] = {}


def flop_calibration(kernel: str, validate: bool = True) -> float:
    """Per-kernel correction factor for the analytic FLOP model, derived
    from a compiled micro cell's ``cost_analysis()`` (dryrun's calibration
    path).  XLA may report whole-program or single-loop-body FLOPs; the
    ratio is taken against whichever analytic granularity it is closest to
    in log space, then clipped — the analytic model stays authoritative,
    cost_analysis corrects its constant factor.  Cached per process."""
    if kernel in _CALIB:
        return _CALIB[kernel]
    import jax

    space = SPACES[kernel]
    shape = _MICRO_SHAPES[kernel]
    cfg = _MICRO_CONFIGS[kernel]
    interpret = jax.default_backend() == "cpu"
    fn, args = _kernel_fn(kernel, shape, cfg, interpret)
    if validate:
        jax.block_until_ready(fn(*args))       # config plumbing really runs
    ratio = 1.0
    try:
        comp = jax.jit(fn).lower(*args).compile()
        ca = comp.cost_analysis()
        reported = float((ca or {}).get("flops", 0.0))
        if reported > 0:
            total = space.flops_interpret(shape, cfg)
            per_step = total / max(1, space.grid_steps(shape, cfg))
            cand = [reported / total, reported / per_step]
            ratio = min(cand, key=lambda r: abs(math.log(max(r, 1e-12))))
            ratio = min(4.0, max(0.25, ratio))
    except Exception:                                      # pragma: no cover
        pass                # cost_analysis unavailable: analytic model as-is
    _CALIB[kernel] = ratio
    return ratio


# ---------------------------------------------------------------- estimation
def _alignment_util(cfg: Dict[str, int]) -> float:
    """MXU utilization degradation for tile dims below the 128 lane width."""
    util = 1.0
    for v in cfg.values():
        util *= min(1.0, v / MXU_LANE)
    return max(util, 1.0 / 64.0)


def estimate_time(space: KernelSpace, shape: ShapeBucket,
                  cfg: Dict[str, int], profile: DeviceProfile,
                  flop_ratio: float = 1.0) -> float:
    """Interpret-mode roofline: seconds for one kernel call on ``profile``."""
    flops = space.flops(shape, cfg) * flop_ratio
    byts = space.bytes_moved(shape, cfg)
    util = BASE_MXU_UTIL * _alignment_util(cfg)
    t_compute = flops / (profile.flops * util)
    t_memory = byts / (profile.hbm_bw * STREAM_EFF)
    overhead = space.grid_steps(shape, cfg) * GRID_STEP_S
    return max(t_compute, t_memory) + overhead


def _time_on_device(fn: Callable, args: tuple) -> float:
    import jax
    jax.block_until_ready(fn(*args))           # compile + warm
    best = math.inf
    for _ in range(DEVICE_REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# -------------------------------------------------------------------- bench
def bench_shape(kernel: str, shape: ShapeBucket, device_types: List[str],
                *, tiny: bool = False,
                log: Callable[[str], None] = lambda s: None,
                ) -> Dict[str, Measurement]:
    """Sweep every feasible config of ``kernel`` on one shape bucket and
    return the best Measurement per requested device type.

    On a matching real accelerator the winner is wall-clocked; for every
    other requested type (and always on CPU) the winner is the roofline
    estimate for that type's profile.
    """
    space = SPACES[kernel]
    local = on_device_type()
    ratio = flop_calibration(kernel)
    best: Dict[str, Measurement] = {}
    for cfg in space.configs(tiny=tiny):
        useful = space.useful_flops(shape)
        for dt in device_types:
            prof = PROFILES[dt]
            if not space.feasible(shape, cfg, dt):
                continue
            if dt == local:
                fn, args = _kernel_fn(kernel, shape, cfg, interpret=False)
                try:
                    t = _time_on_device(fn, args)
                except Exception as e:         # config uncompilable on HW
                    log(f"  {kernel}/{shape.name} {cfg} on {dt}: {e}")
                    continue
                mode = "device"
            else:
                t = estimate_time(space, shape, cfg, prof, ratio)
                mode = "interpret"
            m = Measurement(config=dict(cfg), time_s=t,
                            flops=space.flops(shape, cfg) * ratio,
                            useful_flops=useful,
                            bytes=space.bytes_moved(shape, cfg), mode=mode)
            cur = best.get(dt)
            if cur is None or m.time_s < cur.time_s:
                best[dt] = m
    return best


def configs_tried(kernel: str, shape: ShapeBucket, device_type: str,
                  tiny: bool = False) -> int:
    space = SPACES[kernel]
    return sum(1 for cfg in space.configs(tiny=tiny)
               if space.feasible(shape, cfg, device_type))
