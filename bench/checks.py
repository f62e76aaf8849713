"""The comparisons that decide ``correct``: the numbers each driver holds
against its cell's limits."""
from __future__ import annotations

from statistics import median

# leaves whose reference gradient is under this share of the median leaf's
# move under Adam by round-off alone and are left out of the change
STILL_LEAF = 1e-3


def leaf_gap(prog: dict, ref: dict, leaves=None) -> float:
    """Worst leaf's gap between the program's norm and the reference's, over
    the reference's norm of that leaf or of the median leaf, the larger."""
    med = median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med)
               for k in (ref if leaves is None else leaves))


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold "losses" (one per step), "first_grad"
    and "change" (a norm per leaf)."""
    g = ref["first_grad"]
    med = median(g.values())
    moving = [k for k, v in g.items() if v >= STILL_LEAF * med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"], strict=True)),
        "grad_norm_gap": leaf_gap(prog["first_grad"], g),
        "update_norm_gap": leaf_gap(prog["change"], ref["change"], moving),
    }
