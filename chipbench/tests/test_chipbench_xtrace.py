"""The trace reduction on a small trace recorded on a v5e
(``chipbench/tools/record_trace.py``), and its interval arithmetic."""
import os

import pytest

from chipbench.harness import xtrace

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


def test_union_and_gaps():
    busy = xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)])
    assert busy == [(0, 3), (5, 8), (10, 12)]
    assert xtrace.gaps(busy, -1, 11) == [(-1, 0), (3, 5), (8, 10)]
    assert xtrace.gaps([], 0, 4) == [(0, 4)]


def test_gap_label_names_what_the_host_did():
    host = [(0, 100, "chipbench.step"), (5, 45, "np.asarray"),
            (10, 20, "TransferToDevice"), (21, 38, "TransferToDevice"),
            (50, 52, "tiny")]
    assert xtrace.label_gap((12, 38), host) == "TransferToDevice"
    assert xtrace.label_gap((1, 48), host) == "np.asarray | TransferToDevice"
    assert xtrace.label_gap((49, 53), host) == "tiny"
    assert xtrace.label_gap((60, 90), host) == "chipbench.step"
    assert xtrace.label_gap((200, 300), host) == "no host activity recorded"


def test_names():
    assert xtrace.op_name("%fusion.12 = bf16[8]{0} fusion(%a)") == "fusion.12"
    assert xtrace.module_name("jit__lambda(1079696)") == "jit__lambda#1079"


def _device_ops():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TRACE)
    plane = pd.find_plane_with_name("/device:TPU:0")
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    return [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def test_summary_of_recorded_trace():
    s = xtrace.summarize(TRACE)
    assert s.chips == 1 and s.steps == 3
    assert 0 < s.busy_s < s.window_s
    # busy is the union of the op intervals inside the window
    ops = _device_ops()
    lo = min(a for a, _ in ops)
    assert s.busy_s <= sum(b - a for a, b in xtrace.union(ops)) * 1e-9 + 1e-12
    # the three matmul pairs dominate the device time
    names = [n for n, _ in s.device_ops]
    assert any("fusion" in n for n in names[:2])
    assert sum(t for _, t in s.device_ops) == pytest.approx(s.busy_s, rel=0.05)
    # the recorder's first step compiled the eager cast inside the window:
    # the longest gap is that compile, the next three the host pauses
    assert s.idle_gaps[0][0].startswith("XLA::TPU run backend")
    assert s.idle_gaps[0][1] > 0.05
    assert all(g[0].startswith("chipbench.host_pause")
               for g in s.idle_gaps[1:4])
    assert all(g[1] > 0.009 for g in s.idle_gaps[1:4])
    assert 0.0 < s.idle_share < 1.0 and lo > 0
