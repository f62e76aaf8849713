"""Reduction of a profiler trace (``.xplane.pb``) to device busy and idle time.

The harness wraps the timed window in a host annotation ``bench.window`` and
each host phase inside it in ``bench.<phase>``. Here a device's busy time is
the union of the intervals of the operations on its ``XLA Ops`` line, clipped
to the window; the idle gaps are the holes in that union, each named by the
host phase that overlaps it most. An op's own time leaves out the ops nested
in it, as a loop's body is in the loop.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
TOP = 10
_SHAPE = re.compile(r"\w+\[[\d,]*\]")


@dataclass
class Summary:
    window_s: float  # length of the traced window
    busy_s: float  # device busy time in the window, mean over the chips
    busy_by_chip: list
    device_ops: list  # [[op name, seconds per chip], ...], the TOP longest
    idle_gaps: list  # [[host phase, seconds], ...], the TOP longest gaps


def find_xplane(trace_dir: Path) -> Path:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``, or
    ``trace_dir`` itself when it is the file."""
    if Path(trace_dir).is_file():
        return Path(trace_dir)
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Merged, sorted ``[start, end]`` intervals covering ``intervals``."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def gaps(busy: list, lo: float, hi: float) -> list:
    """The holes of the merged intervals ``busy`` within ``[lo, hi]``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def short_name(hlo: str) -> str:
    """``"%fusion.5 = (f32[4,32]{...}, ...) fusion(...)"`` -> ``"fusion.5 f32[4,32]"``."""
    name, _, rest = hlo.partition(" = ")
    shape = _SHAPE.search(rest)
    return name.lstrip("%") + (f" {shape.group(0)}" if shape else "")


def self_times(ops: list) -> dict:
    """Each op's time less that of the ops nested in it (a loop holds its
    body's ops on the same line), summed by name, in nanoseconds."""
    out: dict = defaultdict(float)
    stack: list = []  # [name, start, end, time of direct children]

    def close(item):
        out[item[0]] += (item[2] - item[1]) - item[3]

    for name, s, e in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    for item in stack:
        close(item)
    return out


def summarize(trace_dir: Path, n_chips: int) -> Summary:
    """Busy and idle time of the first ``n_chips`` TPUs over the window.

    The device's clock runs a little apart from the host's (a millisecond
    or two behind in the traces seen). No device op can start before the
    first step was dispatched, so where one appears to, the device's times
    are moved later by that much."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(trace_dir)))
    host, devices = [], {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            host = [ev for ev in _events(plane) if ev[0].startswith("bench.")]
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_chips:
            devices[int(m.group(1))] = list(_events(plane, OPS_LINE))
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    if len(devices) < n_chips or not all(devices.values()):
        raise ValueError(f"the trace holds ops of {len(devices)} TPUs, not {n_chips}")
    lo, hi = windows[0]
    phases = [(name, s, e) for name, s, e in host if name != WINDOW]
    first_dispatch = min((s for name, s, _ in phases if name == "bench.dispatch"), default=lo)
    shift = max(0.0, first_dispatch - min(s for ops in devices.values() for _, s, _ in ops))

    busy_by_chip, op_time, idle = [], defaultdict(float), []
    for chip in sorted(devices):
        ops = [(name, max(s + shift, lo), min(e + shift, hi))
               for name, s, e in devices[chip] if e + shift > lo and s + shift < hi]
        for name, ns in self_times(ops).items():
            op_time[short_name(name)] += ns / 1e9 / n_chips
        busy = union([[s, e] for _, s, e in ops])
        busy_by_chip.append(sum(e - s for s, e in busy) / 1e9)
        for s, e in gaps(busy, lo, hi):
            idle.append([_phase_of(phases, s, e), (e - s) / 1e9])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(window_s=(hi - lo) / 1e9, busy_s=sum(busy_by_chip) / n_chips,
                   busy_by_chip=busy_by_chip,
                   device_ops=[[name, secs] for name, secs in top_ops],
                   idle_gaps=sorted(idle, key=lambda g: -g[1])[:TOP])


def _phase_of(phases: list, s: float, e: float) -> str:
    """The host phase that overlaps ``[s, e]`` most, or ``"no bench phase"``."""
    best, name = 0.0, "no bench phase"
    for phase, ps, pe in phases:
        overlap = min(e, pe) - max(s, ps)
        if overlap > best:
            best, name = overlap, phase
    return name
