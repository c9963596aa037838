"""Put the chip's idle time in a traced window down to the engine's phases.

The program's engine marks its phases with profiler annotations named
``engine.<phase>`` (``repro.obs.trace.scope``), which land on the host
plane of the ``.xplane.pb`` beside the driver's ``chipbench.window``, on
the clock of the device's ``XLA Ops``.  Here every idle nanosecond of
device 0 inside the window (``xtrace.union``/``xtrace.gaps``, as
``xtrace.summarize`` counts it) goes to the innermost engine scope open at
that instant on the thread that opened the window:

  * ``wait``: ``engine.dispatch`` or ``engine.wait``, the host waiting on
    the chip (the upload of the call's host arguments, then the program);
  * ``host``: any other ``engine.*`` scope, the engine's own Python;
  * ``outside``: no scope, the driver's own code.

The three add up to ``1 - busy / window``.  A trace whose window holds no
``engine.*`` event (a program without the scopes) gives None.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from chipbench.harness import xtrace

PREFIX = "engine."
WAIT = ("engine.dispatch", "engine.wait")


@dataclass
class IdleSplit:
    window_s: float
    busy_s: float                  # device 0's busy seconds in the window
    # idle seconds per innermost scope (None: outside every scope)
    by_scope: Dict[Optional[str], float] = field(default_factory=dict)

    def share(self, kind: str) -> float:
        """Share of the window that is idle in ``kind`` (wait, host or
        outside)."""
        return sum(s for name, s in self.by_scope.items()
                   if _kind(name) == kind) / self.window_s


def _kind(name: Optional[str]) -> str:
    if name is None:
        return "outside"
    return "wait" if name in WAIT else "host"


def innermost(scopes: List[Tuple[float, float, str]], lo: float,
              hi: float) -> List[Tuple[float, float, Optional[str]]]:
    """``[lo, hi)`` cut into pieces, each with the innermost of the nested
    ``scopes`` open over it (None where none is)."""
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: List[Tuple[float, str]] = []          # (end, name), innermost last
    t = lo

    def upto(x: float) -> None:
        nonlocal t
        x = min(x, hi)
        if x > t:
            out.append((t, x, stack[-1][1] if stack else None))
            t = x

    for s, e, name in sorted(scopes, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            upto(stack[-1][0])
            stack.pop()
        upto(s)
        stack.append((e, name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(hi)
    return out


def attribute(path: str, *, window: str = "chipbench.window"
              ) -> Optional[IdleSplit]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chip = pd.find_plane_with_name("/device:TPU:0")
    host = pd.find_plane_with_name("/host:CPU")
    device = (None if chip is None else
              next((line for line in chip.lines if line.name == "XLA Ops"),
                   None))
    if device is None or host is None:
        return None
    win, scopes = None, []
    for line in host.lines:
        evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ev in line.events]
        found = next(((s, e) for s, e, name in evs if name == window), None)
        if found is not None:
            win = found
            scopes = [ev for ev in evs if ev[2].startswith(PREFIX)]
            break
    if win is None:
        return None
    lo, hi = win
    scopes = [ev for ev in scopes if ev[1] > lo and ev[0] < hi]
    if not scopes:
        return None
    busy = xtrace.union([
        (max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi))
        for ev in device.events
        if ev.start_ns < hi and ev.start_ns + ev.duration_ns > lo])
    idle = xtrace.gaps(busy, lo, hi)
    by_scope: Dict[Optional[str], float] = {}
    pieces, i = innermost(scopes, lo, hi), 0
    for gs, ge in idle:
        while pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            ps, pe, name = pieces[j]
            by_scope[name] = (by_scope.get(name, 0.0)
                              + (min(pe, ge) - max(ps, gs)) * 1e-9)
            j += 1
    return IdleSplit(window_s=(hi - lo) * 1e-9,
                     busy_s=sum(e - s for s, e in busy) * 1e-9,
                     by_scope=by_scope)
