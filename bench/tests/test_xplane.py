"""The reduction from a profiler trace to device busy and idle time, on
intervals worked by hand and on a small trace recorded on a TPU v5e by
``bench/record_testdata.py``: five steps of a 2048 x 2048 bf16 matmul, each
followed by a 20 ms host sleep."""
import pytest

import harness
import xplane

TRACE = harness.BENCH / "testdata" / "window.xplane.pb"


def test_union_and_gaps():
    busy = xplane.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert busy == [[0, 3], [5, 8]]
    assert xplane.gaps(busy, -1, 10) == [[-1, 0], [3, 5], [8, 10]]


def test_self_times_leave_out_nested_ops():
    ops = [("loop", 0, 10), ("a", 1, 3), ("b", 4, 8), ("inner", 5, 6), ("c", 12, 13)]
    assert dict(xplane.self_times(ops)) == {"loop": 4, "a": 2, "b": 3, "inner": 1, "c": 1}


def test_short_name():
    hlo = "%fusion.5 = (f32[4,32]{1,0:T(8,128)}, bf16[2]{0}) fusion(f32[4] %x), kind=kLoop"
    assert xplane.short_name(hlo) == "fusion.5 f32[4,32]"


def test_recorded_trace():
    from jax.profiler import ProfileData

    s = xplane.summarize(TRACE, 1)
    # the module executions on the device's other line bound the busy time
    modules = [ev.duration_ns for pl in ProfileData.from_file(str(TRACE)).planes
               if pl.name == "/device:TPU:0" for line in pl.lines
               if line.name == "XLA Modules" for ev in line.events]
    assert len(modules) == 5
    assert s.busy_s == pytest.approx(sum(modules) / 1e9, rel=0.01)
    assert s.busy_s < s.window_s == pytest.approx(0.108581029)
    # the five longest gaps are the host's 20 ms sleeps between steps
    assert [g[0] for g in s.idle_gaps[:5]] == ["bench.sleep"] * 5
    assert all(0.020 < g[1] < 0.023 for g in s.idle_gaps[:5])
    assert s.device_ops[0][0] == "fusion bf16[2048,2048]"
    assert sum(secs for _, secs in s.device_ops) == pytest.approx(s.busy_s, rel=1e-6)
