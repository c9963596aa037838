"""Run a cell through the benchmark's command once per seed, and the spreads.

    python3 chipbench/tools/spread.py WORKLOAD SECONDS TRACE SETS SEED...

Each run is its own process, as the driver makes it; this parent never
touches JAX, so each child has the chip.  The seeds are split into ``SETS``
equal sets in order (give the same seeds to each set to repeat them).  Every
run's result line, exit code and wall time go to standard output and to
``chiprun_out/spread.<WORKLOAD>.jsonl``; then, per set and metric, the
median and the quartile spread ``(q3 - q1) / median`` with the quartiles of
``statistics.quantiles(values, n=4)``.
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv) -> int:
    name, seconds, trace, sets = argv[1], argv[2], argv[3], int(argv[4])
    seeds = argv[5:]
    out = os.path.join(ROOT, "chiprun_out", f"spread.{name}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    results = []
    for i, seed in enumerate(seeds):
        t0 = time.time()
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
             "--workload", name, "--seed", seed, "--seconds", seconds,
             "--trace", trace], cwd=ROOT, capture_output=True, text=True)
        lines = p.stdout.strip().splitlines()
        try:
            res = json.loads(lines[-1]) if lines else None
        except ValueError:
            res = None
        rec = {"set": i * sets // len(seeds), "seed": seed, "rc": p.returncode,
               "wall_s": time.time() - t0, "result": res,
               "stderr_tail": p.stderr[-1500:] if res is None else p.stderr[-300:]}
        results.append(rec)
        print(json.dumps(rec), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    for s in range(sets):
        runs = [r["result"] for r in results if r["set"] == s and r["result"]]
        print(f"set {s}: {len(runs)} runs, correct "
              f"{sum(bool(r['correct']) for r in runs)}")
        for m in sorted({k for r in runs for k in r["metrics"]}):
            vals = [r["metrics"][m]["value"] for r in runs if m in r["metrics"]]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"  {m}: median {med!r} spread {sp!r} values {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
