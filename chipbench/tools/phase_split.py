"""One traced run of a cell, with the chip's idle time split by engine phase.

    python3 chipbench/tools/phase_split.py WORKLOAD SECONDS SEED

Runs the cell's driver once, traced as ``--trace 1`` runs it, and reads
besides what the result line does not carry: the trace's idle time by
engine phase (``harness/phases.py``), read before the trace is reduced and
deleted, and the change over the window of the engine's
``EngineStats.host_arg_bytes`` per ``step()`` call.  One JSON line, with
the result line's metrics, counts and breakdown, the end-to-end metrics of
the traced window, the split and the upload, goes to standard output and
to ``chiprun_out/phase_split.<WORKLOAD>.jsonl``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from contextlib import contextmanager

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv) -> int:
    import importlib

    import repro.serve
    from chipbench.harness import cli, common, phases
    name, seconds, seed = argv[1], float(argv[2]), int(argv[3])
    found = cli.load_cell(name)
    devices = cli.accelerator(int(found["cell"]["chips"]))
    cli.use_cache()

    seen = {"steps": 0}

    class Engine(repro.serve.PagedEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            seen["engine"] = self

        def step(self):
            seen["steps"] += 1
            return super().step()

    repro.serve.PagedEngine = Engine
    profiled, reduce_trace = common.profiled, common.reduce_trace

    @contextmanager
    def window(run):
        stats = seen["engine"].stats
        b0, s0 = stats.host_arg_bytes, seen["steps"]
        with profiled(run) as box:
            yield box
        steps = seen["steps"] - s0
        seen["upload_mb_per_step"] = ((stats.host_arg_bytes - b0) / 1e6 / steps
                                      if steps else None)

    def split_then_reduce(box):
        if box.get("path"):
            seen["split"] = phases.attribute(box["path"])
        return reduce_trace(box)

    common.profiled, common.reduce_trace = window, split_then_reduce
    dev = devices[0]
    run = common.Run(name=name, config=found["config"],
                     traffic=found["traffic"], seed=seed, seconds=seconds,
                     trace=True, t0=T0, out_dir=os.path.join(cli.OUT_DIR, name),
                     clock=common.CompileClock(),
                     peaks=common.peaks(dev.device_kind))
    driver = importlib.import_module(
        f"chipbench.drivers.{found['traffic']['kind']}")
    res = driver.run(run)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = cli.result_line(res, cli.metrics_for(found["bench"], name, True),
                           device, True)
    split = seen.get("split")
    line.update(
        seed=seed, end_to_end=res["end_to_end"],
        host_upload_mb_per_step=seen.get("upload_mb_per_step"),
        idle_split=None if split is None else {
            "window_s": split.window_s, "busy_s": split.busy_s,
            "shares": {k: split.share(k)
                       for k in ("wait", "host", "outside")},
            "by_scope_s": {str(k): v for k, v in sorted(
                split.by_scope.items(), key=lambda kv: -kv[1])}})
    out = os.path.join(ROOT, "chiprun_out", f"phase_split.{name}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
