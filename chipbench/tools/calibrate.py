"""Read a cell's compared numbers over many seeds, and its control's.

    python3 chipbench/tools/calibrate.py WORKLOAD SECONDS SEEDS CONTROL_SEEDS

``SEEDS`` and ``CONTROL_SEEDS`` are comma-separated.  One process runs the
cell's driver once per seed with a window of ``SECONDS`` (set-up, window
and check as in a benchmark run) and, on the control seeds, also reads the
control on the same sample.  Each seed's readings go to standard output and
to ``chiprun_out/calibrate.<WORKLOAD>.jsonl`` as one JSON line.  The limits
in the traffic file are set from these readings (``PERF.md``).
"""
from __future__ import annotations

import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv) -> int:
    import importlib
    from chipbench.harness import check, cli, common
    name, seconds = argv[1], float(argv[2])
    seeds = [int(s) for s in argv[3].split(",")]
    control = {int(s) for s in argv[4].split(",") if s}
    found = cli.load_cell(name)
    devices = cli.accelerator(int(found["cell"]["chips"]))
    cli.use_cache()
    clock = common.CompileClock()
    driver = importlib.import_module(
        f"chipbench.drivers.{found['traffic']['kind']}")
    out = os.path.join(ROOT, "chiprun_out", f"calibrate.{name}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    for seed in seeds:
        t0 = time.perf_counter()
        run = common.Run(name=name, config=found["config"],
                         traffic=found["traffic"], seed=seed, seconds=seconds,
                         trace=False, t0=t0,
                         out_dir=os.path.join(cli.OUT_DIR, name), clock=clock,
                         peaks=common.peaks(devices[0].device_kind),
                         control=seed in control)
        res = driver.run(run)
        line = {"seed": seed, "correct": res["correct"],
                "checks": res["checks"], "control": res["control"],
                "control_correct": (check.verdict(
                    res["control"], found["traffic"]["check"]["limits"])
                    if res["control"] else None),
                "end_to_end": res["end_to_end"], "counts": res["counts"],
                "memory_peak_bytes": res["memory_peak_bytes"],
                "run_s": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
