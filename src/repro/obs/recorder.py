"""The recorder: spans, counters and gauges with a zero-overhead off switch.

One :class:`Recorder` collects everything a run wants to expose:

* **spans** — named intervals on named *tracks* (lanes). Wall-clock spans
  come from the :meth:`Recorder.span` context manager (``time.perf_counter``
  relative to the recorder's origin, so traces start at t=0), which also
  enters ``jax.profiler.TraceAnnotation(name)``: with the JAX profiler on,
  each such span sits in the profile's host plane on the device trace's
  clock, beside the device ops it dispatched; virtual-clock
  spans are filed directly with :meth:`Recorder.add_span` using simulator
  timestamps (the discrete-event engine's virtual seconds). Both are plain
  ``(t0, t1)`` seconds — the Chrome-trace exporter does not care which clock
  produced them, it only requires that spans sharing a track share a clock.
* **counters** — monotonic totals (``count("netsim.bytes_on_wire_mb", x)``).
* **gauges** — last-value-wins observations (``gauge("codec.ratio", r)``).
* **samples** — timestamped counter series for the trace's ``"C"`` events.

The off switch is the module-level :data:`NULL_RECORDER`: call sites fetch
the active recorder once (``rec = obs.get()``) and guard instrumentation
with ``if rec.enabled:`` — a single attribute check when observability is
off, which is what keeps the batched counting path and ``BENCH_netsim.json``
byte-identical with the recorder disabled (pinned by ``tests/test_obs.py``).
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = [
    "NULL_RECORDER",
    "NullRecorder",
    "Recorder",
    "Span",
    "get",
    "recording",
    "set_recorder",
]


class Span:
    """One recorded interval: ``[t0, t1]`` seconds on ``track``'s clock."""

    __slots__ = ("name", "cat", "track", "t0", "t1", "args")

    def __init__(self, name: str, cat: str, track: str,
                 t0: float, t1: float, args: Optional[Dict[str, Any]]) -> None:
        self.name = name
        self.cat = cat
        self.track = track
        self.t0 = t0
        self.t1 = t1
        self.args = args

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return (f"Span({self.name!r}, track={self.track!r}, "
                f"t0={self.t0:.6f}, t1={self.t1:.6f})")


class _NullSpan:
    """The shared no-op context manager the null recorder hands out."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_SPAN = _NullSpan()


class NullRecorder:
    """Observability off: every method is a no-op, ``enabled`` is False.

    Instrumented call sites pay one attribute check (``rec.enabled``) on
    their hot paths and, at coarse granularity (per scenario / per round),
    at most a no-op method call — nothing allocates, nothing accumulates.
    """

    enabled = False

    def span(self, name: str, cat: str = "", track: str = "main",
             **args: Any) -> _NullSpan:
        return _NULL_SPAN

    def add_span(self, name: str, t0: float, t1: float, *,
                 track: str = "main", cat: str = "",
                 args: Optional[Dict[str, Any]] = None) -> None:
        return None

    def count(self, name: str, value: float = 1.0) -> None:
        return None

    def gauge(self, name: str, value: float) -> None:
        return None

    def sample(self, name: str, t: float, value: float,
               track: str = "counters") -> None:
        return None


class Recorder(NullRecorder):
    """Observability on: collect spans/counters/gauges for the sinks.

    ``clock`` labels what wall-clock spans mean (purely descriptive);
    virtual spans carry their own timestamps regardless. The recorder is
    not thread-safe — one per run, like the plan cache.
    """

    enabled = True

    def __init__(self, clock: str = "wall") -> None:
        self.clock = clock
        self.spans: List[Span] = []
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.samples: List[Tuple[str, str, float, float]] = []
        self._origin = time.perf_counter()
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation

    # -- clocks --------------------------------------------------------------
    def now(self) -> float:
        """Seconds since the recorder's origin (the wall-clock span clock)."""
        return time.perf_counter() - self._origin

    # -- spans ---------------------------------------------------------------
    @contextmanager
    def span(self, name: str, cat: str = "", track: str = "main",
             **args: Any) -> Iterator[None]:  # type: ignore[override]
        """A wall-clock span around a ``with`` body, mirrored into the JAX
        profiler's host plane under the same name. Nested spans on one
        track nest by containment in the trace viewer."""
        t0 = self.now()
        try:
            with self._annotation(name):
                yield
        finally:
            self.spans.append(Span(name, cat, track, t0, self.now(),
                                   args or None))

    def add_span(self, name: str, t0: float, t1: float, *,
                 track: str = "main", cat: str = "",
                 args: Optional[Dict[str, Any]] = None) -> None:
        """File a span with explicit timestamps — the virtual-clock path
        (discrete-event engine, fluid simulator slot boundaries)."""
        self.spans.append(Span(name, cat, track, float(t0), float(t1), args))

    # -- metrics -------------------------------------------------------------
    def count(self, name: str, value: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + value

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def sample(self, name: str, t: float, value: float,
               track: str = "counters") -> None:
        """One point of a timestamped counter series (trace ``"C"`` events)."""
        self.samples.append((name, track, float(t), float(value)))

    # -- inspection ----------------------------------------------------------
    def counter_snapshot(self) -> Dict[str, float]:
        return dict(self.counters)

    def spans_by_cat(self) -> Dict[str, Dict[str, float]]:
        """Per-category timing rollup: total seconds and span count — the
        RunReport's "where did the time go" breakdown."""
        out: Dict[str, Dict[str, float]] = {}
        for s in self.spans:
            row = out.setdefault(s.cat or "uncategorized",
                                 {"total_s": 0.0, "spans": 0})
            row["total_s"] += s.duration_s
            row["spans"] += 1
        return out

    def clear(self) -> None:
        self.spans.clear()
        self.counters.clear()
        self.gauges.clear()
        self.samples.clear()


#: The module-level off switch: the active recorder when none is installed.
NULL_RECORDER = NullRecorder()

_active: NullRecorder = NULL_RECORDER


def get() -> NullRecorder:
    """The active recorder (the null recorder unless one is installed).

    Call sites fetch it once per scope and guard on ``.enabled``."""
    return _active


def set_recorder(rec: Optional[NullRecorder]) -> NullRecorder:
    """Install ``rec`` (None restores the null recorder); returns the
    previously active recorder so callers can restore it."""
    global _active
    prev = _active
    _active = rec if rec is not None else NULL_RECORDER
    return prev


@contextmanager
def recording(rec: Recorder) -> Iterator[Recorder]:
    """Scoped install: ``with obs.recording(Recorder()) as rec: ...``."""
    prev = set_recorder(rec)
    try:
        yield rec
    finally:
        set_recorder(prev)
