"""One traced run of a cell, with the chip's busy time split by named scope.

    python3 chipbench/tools/scope_split.py WORKLOAD SECONDS SEED

Runs the cell's driver once, traced as ``--trace 1`` runs it.  The device
trace names each operation by its HLO instruction (``fusion.158``) and its
program; the programs' own HLO, compiled again after the run from the
argument shapes of the engine's first decode and prefill calls, gives each
instruction the ``jax.named_scope`` path it was traced under.  Each device
operation of the window's decode and prefill programs goes to the first of
``SCOPES`` in that path (a fusion's, or else the one most of its fused
instructions carry), or to ``other``; loops and calls, which enclose
operations counted already, are left out.  One JSON line, with the seconds
and milliseconds a step per scope and program, the result line's metrics
and counts, goes to standard output and to
``chiprun_out/scope_split.<WORKLOAD>.jsonl``.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time
from collections import Counter
from contextlib import contextmanager
from typing import Dict, Iterable, List, Tuple

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SCOPES = ("mla.absorb", "mla.attend", "moe.route", "moe.experts", "moe.shared")
PROGRAMS = {"_decode": "jit_paged_decode_step",
            "_prefill": "jit_paged_prefill_chunk"}
ENCLOSING = (" while(", " conditional(", " call(")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scope(op_name: str) -> str:
    return next((s for s in SCOPES if f"/{s}/" in f"/{op_name}/"), "")


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """Each instruction of a compiled module's text -> the first of
    ``SCOPES`` on its ``op_name``; a fusion without one takes the scope most
    of its fused computation's instructions carry."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    inside: Dict[str, Counter] = {}
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            inside[computation] = Counter()
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = _scope(op.group(1)) if op else ""
        if computation is not None and own[name]:
            inside[computation][own[name]] += 1
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    out = {}
    for name, scope in own.items():
        if not scope and name in calls and inside.get(calls[name]):
            scope = inside[calls[name]].most_common(1)[0][0]
        out[name] = scope
    return out


def split(ops: Iterable[Tuple[str, str, float]],
          scopes: Dict[str, Dict[str, str]]) -> Dict[str, Dict[str, float]]:
    """``ops``: (program, HLO instruction text, seconds) of each device
    operation; ``scopes``: program -> instruction -> scope.  Seconds by
    program and scope ("other" where the instruction has none)."""
    from chipbench.harness.xtrace import op_name
    out: Dict[str, Dict[str, float]] = {}
    for program, text, seconds in ops:
        if program not in scopes or any(k in text for k in ENCLOSING):
            continue
        scope = scopes[program].get(op_name(text)) or "other"
        by = out.setdefault(program, {})
        by[scope] = by.get(scope, 0.0) + seconds
    return out


def window_ops(path: str, window: str = "chipbench.window"
               ) -> List[Tuple[str, str, float]]:
    """(program, instruction text, seconds inside the window) of each
    operation on the first chip's ``XLA Ops`` line."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lo = hi = None
    device = None
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window and lo is None:
                        lo, hi = ev.start_ns, ev.start_ns + ev.duration_ns
        elif plane.name.startswith("/device:TPU:") and device is None:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                device = lines
    if device is None or lo is None:
        return []
    modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                      ev.name.partition("(")[0])
                     for ev in device["XLA Modules"].events)
    out, mi = [], 0
    for ev in sorted(device["XLA Ops"].events, key=lambda e: e.start_ns):
        s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
        if e <= s:
            continue
        while mi < len(modules) and modules[mi][1] < ev.start_ns:
            mi += 1
        program = (modules[mi][2] if mi < len(modules)
                   and modules[mi][0] <= ev.start_ns else "")
        out.append((program, ev.name, (e - s) * 1e-9))
    return out


def main(argv) -> int:
    import importlib

    import jax

    import repro.serve
    from chipbench.harness import cli, common
    name, seconds, seed = argv[1], float(argv[2]), int(argv[3])
    found = cli.load_cell(name)
    devices = cli.accelerator(int(found["cell"]["chips"]))
    cli.use_cache()

    calls: Dict[str, tuple] = {}
    stats = {}

    def shape(x):
        return (jax.ShapeDtypeStruct(x.shape, x.dtype)
                if hasattr(x, "shape") and hasattr(x, "dtype") else x)

    class Engine(repro.serve.PagedEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            stats["engine"] = self.stats
            for attr in PROGRAMS:
                fn = getattr(self, attr)

                def recorded(*a, _fn=fn, _attr=attr):
                    calls.setdefault(_attr, (_fn, jax.tree_util.tree_map(shape, a)))
                    return _fn(*a)
                setattr(self, attr, recorded)

    repro.serve.PagedEngine = Engine
    reduce_trace = common.reduce_trace
    seen = {}

    def keep_ops_then_reduce(box):
        if box.get("path"):
            seen["ops"] = window_ops(box["path"])
        return reduce_trace(box)

    common.reduce_trace = keep_ops_then_reduce
    dev = devices[0]
    run = common.Run(name=name, config=found["config"],
                     traffic=found["traffic"], seed=seed, seconds=seconds,
                     trace=True, t0=T0, out_dir=os.path.join(cli.OUT_DIR, name),
                     clock=common.CompileClock(),
                     peaks=common.peaks(dev.device_kind))
    driver = importlib.import_module(
        f"chipbench.drivers.{found['traffic']['kind']}")
    res = driver.run(run)
    scopes = {}
    for attr, (fn, args) in calls.items():
        text = fn.lower(*args).compile().as_text()
        scopes[PROGRAMS[attr]] = instruction_scopes(text)
    by = split(seen.get("ops", []), scopes)
    steps = res["layer"]["steps"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = cli.result_line(res, cli.metrics_for(found["bench"], name, True),
                           device, True)
    st = stats.get("engine")
    line.update(
        seed=seed, end_to_end=res["end_to_end"],
        scope_s=by,
        scope_ms_per_step={p: {k: 1e3 * v / steps for k, v in d.items()}
                           for p, d in by.items()} if steps else None,
        expert_rows=list(st.expert_rows) if st else None,
        routed_pairs=st.routed_pairs if st else None)
    out = os.path.join(ROOT, "chiprun_out", f"scope_split.{name}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
