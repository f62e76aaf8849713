#!/usr/bin/env python
"""Device time of a train cell's step by layer scope, the Mamba2 scopes included.

Usage (from the repo root, after a ``--save`` run on a TPU)::

  python3 bench/record_scopes.py --workload <cell> --seed <n> --save DIR
  python3 tools/ssm_share.py DIR

``bench/scopes.py`` splits a step by the layers its readers report, which do
not include the Mamba2 mixer: its ops count there as ``other``. This joins
the same two tables that ``record_scopes.py --save`` keeps, the compiled
step's text and each op's own device time in the traced window, and prints
one JSON line per cell: ms per step for each scope of the step, ``ssm``
(projections, conv, gated norm) apart from ``ssm.ssd`` (the chunked scan
inside it) and ``attention`` apart from ``attention.flash``, each split into
forward, remat and backward, and their sum against the busy time.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import scopes  # noqa: E402  (bench/scopes.py)
from repro.obs import scopes as names  # noqa: E402

SCOPES = (names.ATTENTION, names.ATTENTION_FLASH, names.MLP, names.SSM, names.SSM_SSD,
          names.LM_HEAD_LOSS, names.CLIP, names.UPDATE, names.GOSSIP)


def scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` on the path ``op_name``, or ``other``."""
    found = "other"
    for segment in (op_name or "").split(";")[0].split("/"):
        while segment.endswith(")") and "(" in segment:
            segment = segment[segment.index("(") + 1:-1]
        if segment in SCOPES:
            found = segment
    return found


def split(hlo_text: str, op_self_s: dict, steps: int) -> dict:
    op_names = scopes.op_names(hlo_text)
    ms = defaultdict(float)
    for name, secs in op_self_s.items():
        op = op_names.get(name, "")
        scope = scope_of(op)
        ms[scope] += 1e3 * secs / steps
        ms[f"{scope}.{scopes.phase_of(op)}"] += 1e3 * secs / steps
    return dict(sorted(ms.items()))


def main() -> None:
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    saved = Path(sys.argv[1])
    for hlo in sorted(saved.glob("*.hlo.txt")):
        cell = hlo.name[: -len(".hlo.txt")]
        times = json.loads((saved / f"{cell}.self_s.json").read_text())
        ms = split(hlo.read_text(), times["op_self_s"], times["steps"])
        print(json.dumps({"workload": cell, "steps": times["steps"],
                          "busy_ms": 1e3 * times["busy_s"] / times["steps"],
                          "sum_ms": sum(v for k, v in ms.items() if k in SCOPES + ("other",)),
                          "ms_per_step": ms}))


if __name__ == "__main__":
    main()
