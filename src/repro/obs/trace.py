"""In-process trace recorder: spans, instants, and counters on one
monotonic timebase, exported as Chrome-trace / Perfetto JSON.

Design constraints (ISSUE 8):

  * **Low overhead** — recording one event is a tuple append; no
    dictionaries are built and no timestamps are converted until
    :meth:`Tracer.to_chrome`.  Every instrumentation site in the repo is
    guarded by ``if tracer is not None``, so a disabled tracer costs a
    single pointer comparison and the instrumented code paths draw the
    same rng stream and produce bit-identical results (asserted in
    ``tests/test_obs.py``).
  * **One timebase per tracer** — simulators pass *sim-time* seconds
    straight from their event loop; runtime components (engine,
    trainer, scheduler) pass :meth:`Tracer.now`, wall-clock seconds
    since tracer creation.  Never mix the two in one tracer.
  * **Device time comes from the profiler, not from here** — a host
    span around an asynchronous dispatch ends before the device work it
    launched.  So the engine's phases are :func:`scope` spans: each is a
    ``jax.profiler.TraceAnnotation`` named ``engine.<phase>``, which a
    ``jax.profiler`` session records on the ``/host:CPU`` plane of its
    ``.xplane.pb``, on the clock of the device's ``XLA Ops``.  Its times
    are relative to the session's start, not to any system clock, so a
    tracer cannot share that clock by reading one; where a tracer is
    given, the scope records the same interval on it as well, on the
    tracer's own timebase.
  * **Groups and tracks** — every event lives on a ``(group, track)``
    pair which export maps to a Chrome ``(pid, tid)`` with
    ``process_name`` / ``thread_name`` metadata, so Perfetto renders one
    swimlane per device, replica, job, or pipeline stage.  Conventions
    used across the repo:

      ==========  =======================  =============================
      group       track                    emitted by
      ==========  =======================  =============================
      stage       generation/env/reward/   simulators + AsyncGRPOTrainer
                  train/sync               (pipeline-stage overlap)
      replica     ``r{i}`` or              simulators (per-device busy
                  ``{job}/r{i}``           time; Σdur == ledger busy)
      sim/pool    plan                     drain→commit swap windows
      scheduler   pool                     schedule_pool / replan_pool
      engine      step/admit/reserve/      PagedEngine (wall-clock):
                  decode/weights/inputs/   ``scope`` spans, and the
                  dispatch/sample/wait/    admission/weights instants
                  bookkeep/prefill/        and the pages counter
                  finish
      jobs        ``{job}``                ControlPlane admission
      ==========  =======================  =============================
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation


class TraceError(RuntimeError):
    """Raised on mismatched ``begin``/``end`` nesting."""


class Tracer:
    """Append-only event recorder; see the module docstring for the
    group/track conventions and the one-timebase rule."""

    def __init__(self, meta: Optional[Dict[str, Any]] = None):
        self._wall0 = time.perf_counter()
        # (ph, group, track, name, t_s, dur_s, args) — Chrome phase
        # letters: X complete-span, B/E begin/end, i instant, C counter.
        self._events: List[Tuple] = []
        self._open: Dict[Tuple[str, str], List[str]] = {}
        # streaming consumers (e.g. the health monitor): called with the
        # raw event tuple fields on every record.  Empty by default, so
        # the recording hot path stays a tuple append plus one falsy
        # check.
        self._sinks: List[Any] = []
        # free-form run metadata (e.g. the simulator's conservation
        # ledger) — exported under Chrome's "otherData" key so the
        # analyzer can cross-check trace-derived quantities against it.
        self.meta: Dict[str, Any] = dict(meta or {})

    # ------------------------------------------------------------ recording
    def now(self) -> float:
        """Wall-clock seconds since tracer creation (runtime timebase).
        Simulators must NOT use this — they pass sim-time directly."""
        return time.perf_counter() - self._wall0

    def add_sink(self, fn: Any) -> None:
        """Register a streaming consumer called as
        ``fn(ph, group, track, name, t, dur, args)`` on every recorded
        event (the health monitor's ``on_trace_event`` fits this)."""
        self._sinks.append(fn)

    def _feed(self, ev: Tuple) -> None:
        for fn in self._sinks:
            fn(*ev)

    def span(self, group: str, track: str, name: str, t: float,
             dur: float, **args: Any) -> None:
        """A complete span ``[t, t+dur)`` (seconds) on ``group/track``."""
        self._events.append(("X", group, track, name, t, dur, args))
        if self._sinks:
            self._feed(self._events[-1])

    def begin(self, group: str, track: str, name: str, t: float,
              **args: Any) -> None:
        """Open a nested span; close with :meth:`end` on the same track."""
        self._open.setdefault((group, track), []).append(name)
        self._events.append(("B", group, track, name, t, 0.0, args))
        if self._sinks:
            self._feed(self._events[-1])

    def end(self, group: str, track: str, t: float, **args: Any) -> str:
        """Close the innermost open span on ``group/track``."""
        stack = self._open.get((group, track))
        if not stack:
            raise TraceError(f"end() without begin() on {group}/{track}")
        name = stack.pop()
        self._events.append(("E", group, track, name, t, 0.0, args))
        if self._sinks:
            self._feed(self._events[-1])
        return name

    def instant(self, group: str, track: str, name: str, t: float,
                **args: Any) -> None:
        self._events.append(("i", group, track, name, t, 0.0, args))
        if self._sinks:
            self._feed(self._events[-1])

    def counter(self, group: str, name: str, t: float,
                **values: float) -> None:
        """A sampled counter series (stacked area chart in Perfetto)."""
        self._events.append(("C", group, name, name, t, 0.0, values))
        if self._sinks:
            self._feed(self._events[-1])

    # ------------------------------------------------------------- querying
    @property
    def n_events(self) -> int:
        return len(self._events)

    def open_spans(self) -> Dict[Tuple[str, str], List[str]]:
        """Tracks with unclosed ``begin``s (innermost last); empty when
        every begin/end pair matched — the nesting invariant tests use
        this."""
        return {k: list(v) for k, v in self._open.items() if v}

    def spans(self, group: Optional[str] = None,
              track: Optional[str] = None
              ) -> Iterator[Tuple[str, float, float, Dict[str, Any]]]:
        """Iterate complete spans as ``(name, t, dur, args)``."""
        for ph, g, tk, name, t, dur, args in self._events:
            if ph != "X":
                continue
            if group is not None and g != group:
                continue
            if track is not None and tk != track:
                continue
            yield (name, t, dur, args)

    # -------------------------------------------------------------- export
    def to_chrome(self) -> Dict[str, Any]:
        """Export to the Chrome trace-event *object* format (loadable in
        Perfetto / chrome://tracing).  Seconds become microseconds here;
        groups/tracks become pids/tids with name metadata."""
        pids: Dict[str, int] = {}
        tids: Dict[Tuple[str, str], int] = {}
        out: List[Dict[str, Any]] = []

        def pid(g: str) -> int:
            p = pids.get(g)
            if p is None:
                p = pids[g] = len(pids) + 1
                out.append({"ph": "M", "name": "process_name", "pid": p,
                            "tid": 0, "args": {"name": g}})
            return p

        def tid(g: str, tk: str) -> int:
            t = tids.get((g, tk))
            if t is None:
                p = pid(g)
                t = tids[(g, tk)] = len(tids) + 1
                out.append({"ph": "M", "name": "thread_name", "pid": p,
                            "tid": t, "args": {"name": tk}})
            return t

        for ph, g, tk, name, t, dur, args in self._events:
            ev: Dict[str, Any] = {"ph": ph, "name": name, "pid": pid(g),
                                  "tid": tid(g, tk), "ts": t * 1e6,
                                  "args": dict(args)}
            if ph == "X":
                ev["dur"] = dur * 1e6
            elif ph == "i":
                ev["s"] = "t"          # thread-scoped instant
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": dict(self.meta)}

    def dump(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path``; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, default=str)
        return path


def scope(tracer: Optional[Tracer], name: str, **args: Any):
    """One phase of the serving engine, as a context manager.  Always a
    profiler annotation ``engine.<name>`` (a no-op outside a profiler
    session); with a ``tracer``, also an ``X`` span on its
    ``engine/<name>`` track that carries ``args``."""
    if tracer is None:
        return TraceAnnotation(f"engine.{name}")
    return _traced_scope(tracer, name, args)


@contextmanager
def _traced_scope(tracer: Tracer, name: str,
                  args: Dict[str, Any]) -> Iterator[None]:
    with TraceAnnotation(f"engine.{name}"):
        t0 = tracer.now()
        try:
            yield
        finally:
            tracer.span("engine", name, name, t0, tracer.now() - t0, **args)
