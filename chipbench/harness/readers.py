"""What the per-layer metric readers share.  Each reads the ``layer`` dict
a driver returns: ``stage``, ``window_s``, ``steps``, ``flops``,
``hbm_bytes``, ``peaks``, the driver's own counters, and ``trace`` (a
``xtrace.TraceSummary``, or None when the run was not traced).  A reader
returns None where it finds nothing to read."""
from __future__ import annotations

from typing import Dict, Optional


def traced(layer: Dict, stage: str):
    if layer.get("stage") != stage:
        return None
    return layer.get("trace")


def idle_share(layer: Dict, stage: str) -> Optional[float]:
    t = traced(layer, stage)
    return None if t is None else 100.0 * (1.0 - t.busy_s / t.window_s)


def device_ms_per_step(layer: Dict, stage: str) -> Optional[float]:
    t = traced(layer, stage)
    if t is None or not t.steps:
        return None
    return 1e3 * t.busy_s / t.steps


def share_of_peak(layer: Dict, stage: str, work: str,
                  peak: str) -> Optional[float]:
    """``work`` done in the window over the window's seconds, as a share
    of the chip's published ``peak``; nothing when no work was counted."""
    if layer.get("stage") != stage or not layer.get(work):
        return None
    return 100.0 * layer[work] / layer["window_s"] / layer["peaks"][peak]
