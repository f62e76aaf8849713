#!/usr/bin/env python
"""Share of attention's device time inside the fused flash-attention kernel.

Usage (from the repo root, after a ``--save`` run on a TPU)::

  python3 bench/record_scopes.py --workload <cell> --seed <n> --save DIR
  python3 tools/flash_share.py DIR

``record_scopes.py --save`` keeps, per cell, the compiled step's text
(``<cell>.hlo.txt``) and each op's own device time in the traced window
(``<cell>.self_s.json``). This joins them as ``bench/scopes.py`` does and
prints one JSON line per cell: the ``attention`` layer's own time per step,
the part of it under the ``attention.flash`` scope (the kernel's custom calls
and the XLA ops its wrapper adds around them), the part in the custom calls
alone, each split into forward, remat and backward, and the shares.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import scopes  # noqa: E402  (bench/scopes.py)
from repro.obs.scopes import ATTENTION_FLASH  # noqa: E402
_CUSTOM = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target="tpu_custom_call"')


def share(hlo_text: str, op_self_s: dict, steps: int) -> dict:
    op_names = scopes.op_names(hlo_text)
    kernels = {m.group(1) for m in map(_CUSTOM.match, hlo_text.splitlines()) if m}
    ms = defaultdict(float)
    for name, secs in op_self_s.items():
        op = op_names.get(name, "")
        if scopes.layer_of(op) != scopes.ATTENTION:
            continue
        phase = scopes.phase_of(op)
        parts = ["attention"]
        if ATTENTION_FLASH in op.split("/"):
            parts.append("flash")
            if name in kernels:
                parts.append("kernel")
        for part in parts:
            ms[part] += 1e3 * secs / steps
            ms[f"{part}.{phase}"] += 1e3 * secs / steps
    total = ms["attention"] or float("nan")
    return {"attention_ms": ms["attention"], "flash_ms": ms["flash"],
            "kernel_ms": ms["kernel"], "flash_share": ms["flash"] / total,
            "kernel_share": ms["kernel"] / total,
            "by_phase_ms": {k: v for k, v in sorted(ms.items()) if "." in k}}


def main() -> None:
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    saved = Path(sys.argv[1])
    for hlo in sorted(saved.glob("*.hlo.txt")):
        cell = hlo.name[: -len(".hlo.txt")]
        times = json.loads((saved / f"{cell}.self_s.json").read_text())
        print(json.dumps({"workload": cell,
                          **share(hlo.read_text(), times["op_self_s"], times["steps"])}))


if __name__ == "__main__":
    main()
