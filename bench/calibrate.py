"""Readings that a cell's limits are set from, on the chip, in one process.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
      [--control-seeds 1,2,3] [--seconds 3]

For each seed: the cell's set-up and, with ``--seconds``, a short window, then
the check's numbers of the sound run against the plain reference; on the
control seeds also the numbers of the control in the program's place and of
each fault the driver plants in the reference. One JSON line per seed, then a
summary: the largest sound reading and the smallest control and fault reading
of each number. The limits in bench/limits/ are set between the two.
"""
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    cell = harness.resolve(args.workload)
    harness.require_tpu(cell.chips)
    harness.enable_compile_cache()
    driver = harness.driver_class(cell)
    sound, other = [], {}
    for seed in seeds:
        t0 = time.perf_counter()
        drv = driver(cell, seed)
        if args.seconds:
            drv.window(args.seconds)
        drv.release()
        line = {"seed": seed, "sound": drv.check()}
        sound.append(line["sound"])
        if seed in control_seeds:
            line["control"] = drv.control()
            line.update(drv.faults())
        for k, v in line.items():
            if k not in ("seed", "sound"):
                other.setdefault(k, []).append(v)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del drv
    summary = {"lower": {k: max(s[k] for s in sound) for k in sound[0]}}
    for kind, readings in other.items():
        summary[kind] = {k: min(r[k] for r in readings) for k in readings[0]}
    print(json.dumps({"summary": summary, "seeds": len(sound)}), flush=True)


if __name__ == "__main__":
    main()
