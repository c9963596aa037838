"""Devices, peaks, seeds, the compile clock and the profiler, shared by drivers."""
from __future__ import annotations

import glob
import json
import os
import shutil
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS_FILE = os.path.join(HERE, "peaks.json")


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def seed_key(seed: int):
    """A JAX key from a run seed of any size (the driver's exceed 32 bits)."""
    import jax.numpy as jnp
    return jnp.asarray(np.random.SeedSequence(seed).generate_state(2),
                       jnp.uint32)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and how many such
    events it recorded (any of them inside the window means something was
    not warmed up)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += 1


@dataclass
class Run:
    """One invocation: the cell and its files, the seed and the window."""
    name: str
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    t0: float                          # perf_counter at process start
    out_dir: str                       # scratch inside the checkout
    clock: Optional[CompileClock] = None
    peaks: Dict = field(default_factory=dict)
    control: bool = False              # also read the control (calibration)


@contextmanager
def profiled(run: Run):
    """Trace the block with the profiler when ``run.trace``; yields a dict
    that holds the reduced trace afterwards."""
    import jax
    box: Dict = {}
    if not run.trace:
        yield box
        return
    path = os.path.join(run.out_dir, "trace")
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield box
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    box["path"] = files[0] if files else None


def reduce_trace(box: Dict):
    from chipbench.harness import xtrace
    path = box.get("path")
    if not path:
        return None
    try:
        return xtrace.summarize(path)
    finally:
        shutil.rmtree(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(path)))), ignore_errors=True)
