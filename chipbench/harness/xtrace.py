"""Reduce a profiler trace (``.xplane.pb``) to device busy time and its gaps.

What a TPU trace holds (seen on a v5e, jax 0.9): one plane per chip named
``/device:TPU:<n>`` whose ``XLA Ops`` line has one event per HLO operation
and whose ``XLA Modules`` line has one per program run; and a ``/host:CPU``
plane with a line per host thread, where ``TraceAnnotation`` scopes, the
runtime's transfers (``tpu::System::TransferToDevice``) and compiles appear.
Both planes share one clock.

Busy time is the union of the intervals of the ``XLA Ops`` events inside
the window.  Host-to-device copies of arguments are not device operations:
they show on the host plane only, so the time a chip waits for an upload
counts as idle.  The window is the first host event named ``window``
(the driver's ``TraceAnnotation`` around the measured loop); each idle gap
in it is labelled by the shortest host event that covers at least half of
it and by the name of the shorter events that overlap it most.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    busy_s: float                 # mean over chips of device busy seconds
    window_s: float               # traced window, seconds
    steps: int                    # step annotations that start in the window
    chips: int
    device_ops: List[List] = field(default_factory=list)   # [name, seconds]
    idle_gaps: List[List] = field(default_factory=list)    # [label, seconds]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit__lambda(1079...)`` -> ``jit__lambda#1079``: the program's name
    and the start of its fingerprint, which tells two anonymous jits apart."""
    base, _, rest = event_name.partition("(")
    return f"{base}#{rest[:4]}" if rest else base


def label_gap(gap: Interval, host: List[Tuple[float, float, str]]) -> str:
    """What the host did in ``gap``: the shortest host event that covers at
    least half of it (what the driving thread was in), then the name whose
    shorter events overlap it most in sum (what ran beside it, such as the
    runtime's transfers)."""
    s, e = gap
    half = 0.5 * (e - s)
    cover, beside = None, {}
    for hs, he, name in host:
        ov = min(e, he) - max(s, hs)
        if ov <= 0:
            continue
        if ov >= half:
            if cover is None or he - hs < cover[0]:
                cover = (he - hs, name)
        else:
            beside[name] = beside.get(name, 0.0) + ov
    names = [cover[1]] if cover else []
    beside.pop(names[0] if names else None, None)
    if beside:
        names.append(max(beside, key=beside.get))
    return " | ".join(names) or "no host activity recorded"


def summarize(path: str, *, window: str = "chipbench.window",
              step: str = "chipbench.step", top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host_lines = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name == "/host:CPU":
            host_lines = list(plane.lines)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with XLA Ops")

    host: List[Tuple[float, float, str]] = []
    win: Optional[Interval] = None
    step_starts: List[float] = []
    for line in host_lines:
        for ev in line.events:
            name = ev.name
            if name.startswith("$"):          # Python function events
                continue
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if name == window and win is None:
                win = (s, e)
                continue
            if name == step:
                step_starts.append(s)
            host.append((s, e, name))
    if win is None:
        spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in devices[0]["XLA Ops"].events]
        win = (min(s for s, _ in spans), max(e for _, e in spans))
    lo, hi = win

    busy_total, totals = 0.0, {}
    first_busy: List[Interval] = []
    for i, lines in enumerate(devices):
        modules = []
        if "XLA Modules" in lines:
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              module_name(ev.name))
                             for ev in lines["XLA Modules"].events)
        spans, mi = [], 0
        for ev in sorted(lines["XLA Ops"].events, key=lambda x: x.start_ns):
            iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if iv is None:
                continue
            spans.append(iv)
            if i == 0:
                while mi < len(modules) and modules[mi][1] < ev.start_ns:
                    mi += 1
                mod = (modules[mi][2] if mi < len(modules)
                       and modules[mi][0] <= ev.start_ns else "")
                key = f"{mod}/{op_name(ev.name)}" if mod else op_name(ev.name)
                totals[key] = totals.get(key, 0.0) + (iv[1] - iv[0])
        merged = union(spans)
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            first_busy = merged
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return TraceSummary(
        busy_s=busy_total / len(devices) * 1e-9,
        window_s=(hi - lo) * 1e-9,
        steps=sum(lo <= s < hi for s in step_starts),
        chips=len(devices),
        device_ops=[[k, v * 1e-9] for k, v in
                    sorted(totals.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[label_gap(g, host), (g[1] - g[0]) * 1e-9] for g in idle],
    )
