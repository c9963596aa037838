"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Finds the cell by name, its configuration file, the adapter and the
reference of the configuration's ``architecture``
(``chipbench/adapters/<architecture>.py``,
``chipbench/reference/<architecture>.py``; an architecture without both
is refused before any device work), its traffic file
(``chipbench/traffic/<traffic>.json``), and the driver that the traffic's
``kind`` names (``chipbench/drivers/<kind>.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the window
runs under the profiler and the result carries the per-layer metrics, each
read by ``chipbench/metrics/<name>.py``, which returns nothing where it
finds nothing to read.  The last line of standard output is the result; the
numbers compared with the reference, each beside its limit, close both it
and standard error.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
OUT_DIR = os.path.join(ROOT, ".chipbench")


class Refused(Exception):
    """The run cannot be made here; nothing is printed on stdout."""


def load_cell(name: str) -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        config_data = json.load(f)
    from chipbench.harness import program
    try:
        program.adapter(config_data)
        program.reference(config_data)
    except program.UnknownArchitecture as e:
        raise Refused(str(e)) from None
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"bench": bench, "cell": cell, "config": config_data,
            "traffic": traffic}


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_metric(name: str, layer: Dict) -> Optional[float]:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(layer)


def accelerator(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise Refused("no accelerator: JAX found only the CPU")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def use_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every program however short its compile."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(res: Dict, metrics: List[Dict], device: Dict,
                trace: bool) -> Dict:
    out = {}
    for m in metrics:
        v = (read_metric(m["name"], res["layer"]) if trace
             else res["end_to_end"].get(m["name"]))
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": out, "device": dict(device)}
    summary = res["layer"].get("trace")
    if trace and summary is not None:
        line["device"]["busy_s"] = summary.busy_s
        line["device"]["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["counts"] = res["counts"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res["checks"].items()}
    return line


def main(argv: List[str], t0: float) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        found = load_cell(args.workload)
        devices = accelerator(int(found["cell"]["chips"]))
    except (Refused, OSError, KeyError, StopIteration, RuntimeError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from chipbench.harness import common
    use_cache()
    dev = devices[0]
    run = common.Run(name=args.workload, config=found["config"],
                     traffic=found["traffic"], seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace), t0=t0,
                     out_dir=os.path.join(OUT_DIR, args.workload),
                     clock=common.CompileClock(),
                     peaks=common.peaks(dev.device_kind))
    driver = importlib.import_module(
        f"chipbench.drivers.{found['traffic']['kind']}")
    res = driver.run(run)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = result_line(res, metrics_for(found["bench"], args.workload,
                                        run.trace), device, run.trace)
    print(f"correct: {line['correct']}", file=sys.stderr)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
