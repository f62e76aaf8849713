"""Resolves a benchmark cell to its files, runs it and builds its result line.

Everything about one configuration, traffic mix, per-layer metric or cell sits
in files of its own, found by the names in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the configuration as it is run;
- ``bench/traffic/<traffic>.json``: the traffic mix, which names its driver;
- ``bench/drivers/<driver>.py``: builds the program, drives the timed window
  and compares what it produced with the plain reference;
- ``bench/metrics/<metric>.py``: reads one per-layer metric;
- ``bench/limits/<cell>.json``: the limit of each number the check compares;
  ``null`` for a number that is read and printed but not compared (PERF.md
  says why for each).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict


@dataclass
class Context:
    """What a per-layer metric's reader may read."""

    cell: Cell
    facts: dict  # counts from shapes and the compiled program, by the driver
    window: dict  # "seconds" and "units" (steps or rounds) of the timed window
    peaks: dict  # the device's row of bench/peaks.json
    trace: Optional[object] = None  # xplane.Summary of a --trace 1 run


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(name: str, benchmark: Optional[dict] = None) -> Cell:
    b = benchmark or load_json(ROOT / "BENCHMARK.json")
    matches = [w for w in b["workloads"] if w["name"] == name]
    if not matches:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in b['workloads']]}")
    w = matches[0]
    config = next(c for c in b["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=w["chips"],
        config_name=w["config"], config=load_json(ROOT / config["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in b["end_to_end"] if applies(m, name)],
        per_layer=[m for m in b["per_layer"] if applies(m, name)],
        limits=load_json(BENCH / "limits" / f"{name}.json"),
    )


def import_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def driver_class(cell: Cell):
    driver = cell.traffic["driver"]
    return import_file(BENCH / "drivers" / f"{driver}.py", f"bench_driver_{driver}").Driver


def metric_reader(name: str):
    return import_file(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def device_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices; exits when JAX finds fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max(peaks) if None not in peaks else None


def compared(readings: dict, limits: dict) -> dict:
    """The readings the check compares, each beside its limit."""
    missing = set(readings) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)} in bench/limits/")
    return {k: {"value": v, "limit": limits[k]} for k, v in readings.items()
            if limits[k] is not None}


def execute(cell: Cell, seed: int, seconds: float, trace: bool, devices: list,
            t_start: float) -> tuple[dict, dict]:
    """One run of ``cell`` on ``devices``: set-up, the timed window, the
    check against the reference. Returns the result line as a dict, and
    every number the check read, compared or not."""
    import jax

    from xplane import summarize

    drv = driver_class(cell)(cell, seed)
    setup_s = time.perf_counter() - t_start
    trace_dir = TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        win = drv.window(seconds)
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = summarize(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak = peak_bytes(devices)
    facts = drv.facts()
    drv.release()
    gc.collect()
    readings = drv.check()
    checks = compared(readings, cell.limits)
    correct = win["failed"] == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    kind = devices[0].device_kind
    if trace:
        ctx = Context(cell=cell, facts=facts, window=win, peaks=device_peaks(kind),
                      trace=summary)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(win["metrics"], setup_s=setup_s,
                      peak_hbm_gib=None if peak is None else peak / 2**30)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": win["units"], "failed": win["failed"],
              "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result, readings


def main(argv: list, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the TPU.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(args.workload)
    devices = require_tpu(cell.chips)
    enable_compile_cache()
    result, readings = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                               t_start)
    print(f"correct: {result['correct']} (failed {result['failed']} of "
          f"{result['attempted']})", file=sys.stderr)
    for name, value in readings.items():
        if name not in result["checks"]:
            print(f"read {name}: {value!r} (not compared)", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
