"""The idle split by engine phase (``harness/phases.py``) on a small engine
trace recorded on a v5e (``chipbench/tools/record_engine_trace.py``)."""
import importlib.util
import os

import pytest

from chipbench.harness import phases, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ENGINE = os.path.join(DATA, "engine.xplane.pb")


def test_innermost_cuts_the_window_by_the_open_scope():
    scopes = [(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (6, 8, "d"),
              (11, 20, "e")]
    assert phases.innermost(scopes, -1, 12) == [
        (-1, 0, None), (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"),
        (6, 8, "d"), (8, 10, "a"), (10, 11, None), (11, 12, "e")]
    assert phases.innermost([], 0, 5) == [(0, 5, None)]


def test_split_of_recorded_engine_trace_adds_up_to_the_idle_share():
    split = phases.attribute(ENGINE)
    summary = xtrace.summarize(ENGINE)
    assert split.window_s == pytest.approx(summary.window_s, rel=1e-12)
    assert split.busy_s == pytest.approx(summary.busy_s, rel=1e-9)
    shares = {k: split.share(k) for k in ("wait", "host", "outside")}
    assert sum(shares.values()) == pytest.approx(
        1.0 - summary.busy_s / summary.window_s, abs=1e-6)
    assert shares["wait"] > 0 and shares["host"] > 0
    names = set(split.by_scope) - {None}
    assert {"engine.dispatch", "engine.wait"} <= names
    assert all(n.startswith("engine.") for n in names)
    assert summary.steps == 2
    assert any(n.startswith("jit_paged_decode_step")
               for n, _ in summary.device_ops)


def test_trace_without_engine_scopes_gives_nothing():
    assert phases.attribute(os.path.join(DATA, "small.xplane.pb")) is None


def test_recorder_drops_only_the_named_plane(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "record_engine_trace",
        os.path.join(HERE, "..", "tools", "record_engine_trace.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    from jax.profiler import ProfileData
    small = os.path.join(DATA, "small.xplane.pb")
    with open(small, "rb") as f:
        raw = f.read()
    assert tool.drop_plane(raw, "no such plane") == raw
    cut = tmp_path / "cut.xplane.pb"
    cut.write_bytes(tool.drop_plane(raw, "/host:metadata"))
    names = lambda p: [pl.name for pl in ProfileData.from_file(p).planes]
    assert names(str(cut)) == [n for n in names(small)
                               if n != "/host:metadata"]
    assert "/host:metadata" in names(small)
    assert xtrace.summarize(str(cut)) == xtrace.summarize(small)
