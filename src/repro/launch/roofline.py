"""Roofline-term extraction from AOT-compiled modules (the dry-run profile).

Three terms per (arch × shape × mesh), in seconds:

  compute    = HLO_FLOPs_global / (chips × peak_FLOP/s)
  memory     = HLO_bytes_global / (chips × HBM_bw)
  collective = wire_bytes_per_chip / link_bw
               (≡ assignment's collective_bytes_global / (chips × link_bw))

Sources: ``compiled.cost_analysis()`` for flops/bytes; the optimized HLO text
for collectives (the compiled module is the per-partition SPMD program, so
result shapes are per-device — wire-bytes per op are estimated from them and
the op's semantics).  Whether cost_analysis reports per-device or global
numbers is calibrated empirically once per process (see ``calibrate``).

Hardware constants (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Optional, Tuple

PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * b


def _line_result_bytes(line: str, op: str) -> int:
    """Sum shape bytes on the LHS of '=' (handles tuple results)."""
    lhs = line.split(f" {op}")[0]
    if "=" in lhs:
        lhs = lhs.split("=", 1)[1]
    return sum(_shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(lhs))


def _group_size(line: str) -> Optional[int]:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:                       # iota v2 form: [num_groups, group_size]
        return int(m.group(2))
    return None


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    result_bytes: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Scan optimized HLO for collectives; estimate per-device wire bytes.

    Ring estimates per op (shapes are per-partition):
      all-reduce       2·(g−1)/g · result   (reduce-scatter + all-gather)
      all-gather       (g−1)/g · result     (result = gathered buffer)
      reduce-scatter   (g−1)·result         (input = g · result)
      all-to-all       (g−1)/g · result
      collective-permute  result
    """
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in _COLL_OPS:
            # match `op(`, `op-start(` but not `-done(`
            if f" {op}(" in stripped or f" {op}-start(" in stripped:
                rb = _line_result_bytes(stripped,
                                        op + ("-start" if f" {op}-start(" in
                                              stripped else ""))
                g = _group_size(stripped) or 2
                if op == "all-reduce":
                    wb = 2.0 * (g - 1) / g * rb
                elif op == "all-gather":
                    wb = (g - 1) / g * rb
                elif op == "reduce-scatter":
                    wb = (g - 1) * rb
                elif op == "all-to-all":
                    wb = (g - 1) / g * rb
                else:
                    wb = float(rb)
                st.counts[op] = st.counts.get(op, 0) + 1
                st.result_bytes[op] = st.result_bytes.get(op, 0) + rb
                st.wire_bytes[op] = st.wire_bytes.get(op, 0.0) + wb
                break
    return st


_CALIBRATION: Dict[str, float] = {}


def calibrate_cost_analysis() -> float:
    """Determine whether cost_analysis() reports per-device or global FLOPs.

    Compiles a known matmul sharded over all devices; returns the factor
    (reported_flops / global_flops).  ~1.0 → global semantics;
    ~1/n_devices → per-device (per-partition SPMD module) semantics.
    Cached per process.
    """
    if "factor" in _CALIBRATION:
        return _CALIBRATION["factor"]
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    n = len(jax.devices())
    mesh = make_mesh((n,), ("x",))
    dim = 512
    true_flops = 2 * dim ** 3

    @jax.jit
    def mm(a, b):
        return a @ b

    sh = NamedSharding(mesh, P("x", None))
    a = jax.ShapeDtypeStruct((dim, dim), jnp.float32, sharding=sh)
    b = jax.ShapeDtypeStruct((dim, dim), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, None)))
    comp = mm.lower(a, b).compile()
    ca = comp.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    factor = flops / true_flops if true_flops else 1.0
    _CALIBRATION["factor"] = factor
    return factor


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    hlo_gflops_per_dev: float
    hlo_gbytes_per_dev: float
    wire_gbytes_per_dev: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_gflops: float          # 6·N·D (train) / 2·N·B (decode), global
    useful_flops_ratio: float    # MODEL / (HLO_global)
    collectives: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)
    memory_per_dev_gb: Optional[float] = None
    notes: str = ""

    def to_json(self) -> Dict:
        return asdict(self)


def build_roofline(
    *, arch: str, shape: str, mesh_name: str, n_devices: int,
    cost: Dict, hlo_text: str, model_flops: float,
    mem_per_dev_bytes: Optional[float], calib_factor: float,
    mix_correction_flops: float = 0.0,
    collectives_override: Optional[Dict] = None,
) -> Roofline:
    flops_reported = float(cost.get("flops", 0.0))
    bytes_reported = float(cost.get("bytes accessed", 0.0))
    # Calibration decides semantics: factor ≈ 1/n_calib ⇒ cost_analysis is
    # per-partition (per-device); factor ≈ 1 ⇒ global.
    import jax as _jax
    n_calib = len(_jax.devices())
    per_device = calib_factor < 2.0 / n_calib
    if per_device:
        flops_dev = flops_reported
        bytes_dev = bytes_reported
    else:
        flops_dev = flops_reported / n_devices
        bytes_dev = bytes_reported / n_devices
    # Analytic correction: sequence-mixing flops hidden inside chunked
    # lax.scan loops (XLA cost analysis counts while bodies once).
    flops_dev += mix_correction_flops / n_devices

    coll = parse_collectives(hlo_text)
    if collectives_override is not None:
        coll = CollectiveStats(counts=collectives_override["counts"],
                               result_bytes={},
                               wire_bytes=collectives_override["wire_bytes"])
    wire_dev = coll.total_wire_bytes

    t_c = flops_dev / PEAK_FLOPS
    t_m = bytes_dev / HBM_BW
    t_l = wire_dev / LINK_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_l}
    bottleneck = max(terms, key=terms.get)

    global_flops = flops_dev * n_devices
    ratio = model_flops / global_flops if global_flops > 0 else 0.0
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, n_devices=n_devices,
        hlo_gflops_per_dev=flops_dev / 1e9,
        hlo_gbytes_per_dev=bytes_dev / 1e9,
        wire_gbytes_per_dev=wire_dev / 1e9,
        t_compute=t_c, t_memory=t_m, t_collective=t_l,
        bottleneck=bottleneck, model_gflops=model_flops / 1e9,
        useful_flops_ratio=ratio,
        collectives={k: v / 1e9 for k, v in coll.wire_bytes.items()},
        counts=coll.counts,
        memory_per_dev_gb=(mem_per_dev_bytes / 1e9
                           if mem_per_dev_bytes is not None else None),
    )


def model_flops_for_cell(cfg, shape_spec) -> float:
    """Analytic MODEL_FLOPS for one cell (global, per lowered program):
    train: 6·N_active·tokens;  prefill: 2·N_active·tokens;
    decode: 2·N_active·batch (one token each)."""
    spec = cfg.spec
    n_act = spec.params(active_only=True)
    if shape_spec.kind == "train":
        return 6.0 * n_act * shape_spec.global_batch * shape_spec.seq_len
    if shape_spec.kind == "prefill":
        return 2.0 * n_act * shape_spec.global_batch * shape_spec.seq_len
    return 2.0 * n_act * shape_spec.global_batch


# ------------------------------------------------- loop-trip flop correction
def _avg_causal_ctx(S: int, window: Optional[int]) -> float:
    """Mean attended context per query under causal (+optional SWA) mask."""
    W = min(window, S) if window else S
    # sum_{t=0..S-1} min(t, W) / S
    full = W * (W - 1) / 2.0 + (S - W) * W
    return full / S


def loop_flop_correction(cfg, shape_spec) -> float:
    """Global FLOPs executed inside chunked sequence loops that XLA's cost
    analysis under-counts (while bodies are visited once, not per trip).

    Returns  mix_total · multiplier · (1 − 1/trips)  summed over the
    sequence-mixing mechanisms of the architecture.  multiplier = 4 for
    training (fwd + remat recompute + ~2× backward), 1 for fwd-only.
    """
    kind = shape_spec.kind
    S = shape_spec.seq_len
    B = shape_spec.global_batch
    mult = 4.0 if kind == "train" else 1.0
    total = 0.0

    def attn_term(n_layers, S_q, ctx_len, kv_window, causal=True,
                  kv_cache=False):
        # 4·H·hd·ctx flops per query token per layer (QK^T + PV, fwd)
        if kv_cache:
            # single-token decode lowers UNCHUNKED (blocks.attention Sq==1
            # fast path) — no loop, fully counted by cost_analysis
            return 0.0
        ctx = (_avg_causal_ctx(S_q, kv_window) if causal else ctx_len)
        tokens = B * S_q
        trips = max(1, -(-int(ctx_len) // cfg.kv_chunk))
        flops = 4.0 * cfg.n_heads * cfg.hd * ctx * tokens * n_layers
        return flops * (1.0 - 1.0 / trips)

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        if kind == "decode":
            total += attn_term(cfg.n_layers, 1, S, cfg.attn_window,
                               kv_cache=True)
        else:
            total += attn_term(cfg.n_layers, S, S, cfg.attn_window)
    elif fam == "encdec":
        if kind == "decode":
            total += attn_term(cfg.n_layers, 1, S, None, kv_cache=True)
            total += attn_term(cfg.n_layers, 1, cfg.encoder_seq, None,
                               kv_cache=True)   # cross
        else:
            total += attn_term(cfg.n_layers, S, S, None)
            total += attn_term(cfg.n_layers, S, cfg.encoder_seq, None,
                               causal=False)    # cross
            total += attn_term(cfg.n_encoder_layers, cfg.encoder_seq,
                               cfg.encoder_seq, None, causal=False)
    elif fam == "ssm":
        # chunked mLSTM: per chunk ≈ 6·T²·D + 4·T·D² flops per (b, h, layer)
        T = 64
        D = cfg.hd
        if kind == "decode":
            return 0.0   # single recurrent step, no loop
        nch = max(1, -(-S // T))
        per_bh = nch * (6.0 * T * T * D + 4.0 * T * D * D)
        total += per_bh * B * cfg.n_heads * cfg.n_layers * (1 - 1.0 / nch)
    elif fam == "hybrid":
        if kind == "decode":
            total += attn_term(cfg.n_layers, 1, S, cfg.attn_window,
                               kv_cache=True)
        else:
            total += attn_term(cfg.n_layers, S, S, cfg.attn_window)
            Tc = 128
            nch = max(1, -(-S // Tc))
            ssm = 10.0 * B * S * cfg.d_model * cfg.ssm_state * cfg.n_layers
            total += ssm * (1 - 1.0 / nch)
    return total * mult
