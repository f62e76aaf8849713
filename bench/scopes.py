"""Split of a traced window's device time by the layer scopes of the DFL step.

The program puts a ``jax.named_scope`` at each layer boundary of its jitted
step (``src/repro/obs/scopes.py``). The device trace does not carry the
scope: its ``XLA Ops`` events hold only the HLO text of each instruction.
The scope lives in the compiled executable's ``as_text()``, as each
instruction's ``op_name``, e.g.
``jit(fn)/dfl.grads/transpose(jvp())/while/body/closed_call/checkpoint/attention/...``.
So the split joins two tables: every op's own time in the window (its time
less that of the ops nested in it, ``xplane.self_times``), keyed by HLO
instruction name, and each instruction's ``op_name``.

Attribution: the instruction's ``op_name``, the first where XLA joins
several with ``;``; an instruction the compiler made without one (a loop's
stacking of its outputs, say) takes the ``op_name`` of the instruction that
calls its computation. The op's layer is the innermost of :data:`LAYERS`
on that path, where a transform's wrapper counts as its content
(``transpose(jvp(attention))`` is ``attention``). An op under none of them
is ``other``: the embedding, norms, residual adds and loop control.

A fusion's own ``op_name`` is that of its root alone. Where the ops fused
into it carry layer scopes, the fusion takes the layer most of them carry
(its root's on a tie), and then the ``op_name`` of the first of them: AdamW's
update of a leaf fused with the cast of the master weights to the working
copy is ``dfl.update`` by its ops, though its root, the cast, sits in
``dfl.gossip``. Unscoped ops in a fusion (slicing a layer's weights out of
the scan's stack, say) do not vote.

The scope names are literals here, not imported from the program: a rename
there then fails ``bench/tests/test_scopes.py`` instead of moving a metric
without notice.
"""
from __future__ import annotations

import re
from collections import Counter, defaultdict
from pathlib import Path
from typing import Optional

import xplane

ATTENTION, MLP, HEAD_LOSS = "attention", "mlp", "lm_head_loss"
CLIP, UPDATE, GOSSIP = "dfl.clip", "dfl.update", "dfl.gossip"
LAYERS = (ATTENTION, MLP, HEAD_LOSS, CLIP, UPDATE, GOSSIP)
OTHER = "other"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSED = re.compile(r"\sfusion\(.*\scalls=%?([\w.\-]+)")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
                     r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def _unwrap(segment: str) -> str:
    """``"transpose(jvp(attention))"`` -> ``"attention"``."""
    while segment.endswith(")") and "(" in segment:
        segment = segment[segment.index("(") + 1:-1]
    return segment


def layer_of(op_name: Optional[str]) -> str:
    """The innermost of :data:`LAYERS` on the path ``op_name``, or ``other``."""
    found = OTHER
    for segment in (op_name or "").split(";")[0].split("/"):
        if _unwrap(segment) in LAYERS:
            found = _unwrap(segment)
    return found


def phase_of(op_name: Optional[str]) -> str:
    """``forward``, ``remat`` (a forward recomputed for the backward pass) or
    ``backward``, from the transforms on the path."""
    path = (op_name or "").split(";")[0]
    if "transpose(" not in path:
        return "forward"
    return "remat" if "rematted_computation" in path else "backward"


def _fused_op_name(root: str, fused: list) -> str:
    """The ``op_name`` a fusion is attributed by: the root's own where the
    layer most of the fused ops carry is the root's (on a tie too) or none
    of them carries one; else the first fused op's of that layer."""
    votes = Counter(layer_of(op) for op in fused if layer_of(op) != OTHER)
    if not votes or votes[layer_of(root)] == max(votes.values()):
        return root
    layer = next(layer for layer in LAYERS if votes[layer] == max(votes.values()))
    return next(op for op in fused if layer_of(op) == layer)


def op_names(hlo_text: str) -> dict:
    """Each instruction of a compiled module's ``as_text()`` -> the
    ``op_name`` it is attributed by (``""`` where neither it, the ops fused
    into it, nor a caller of its computation has one)."""
    own, computation_of, callers = {}, {}, defaultdict(list)
    members, fuses = defaultdict(list), {}
    computation = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            words = line.split()
            computation = (words[1] if words[0] == "ENTRY" else words[0]).lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        computation_of[name] = computation
        members[computation].append(name)
        op = _OP_NAME.search(line)
        own[name] = op.group(1).split(";")[0] if op else ""
        for single, several in _CALLED.findall(line):
            for callee in [single] if single else several.split(","):
                callers[callee.strip().lstrip("%")].append(name)
        fused = _FUSED.search(line)
        if fused:
            fuses[name] = fused.group(1)
    for name, computation in fuses.items():
        own[name] = _fused_op_name(own[name], [own[i] for i in members[computation]])

    resolved: dict = {}

    def resolve(name: str, seen: frozenset = frozenset()) -> str:
        if name not in resolved:
            op = own[name]
            if not op:
                for caller in callers.get(computation_of[name], []):
                    if caller not in seen:
                        op = resolve(caller, seen | {name})
                        if op:
                            break
            resolved[name] = op
        return resolved[name]

    return {name: resolve(name) for name in own}


def instruction(event_name: str) -> str:
    """The HLO instruction name of a device op event (its HLO text)."""
    return xplane.short_name(event_name).split(" ")[0]


def window_self_times(trace: Path, n_chips: int) -> dict:
    """Every op's own time within the ``bench.window`` of ``trace``, mean
    per chip, keyed by HLO instruction name; with the window and the clock
    shift as ``xplane.summarize`` reads them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane.find_xplane(trace)))
    host, devices = [], {}
    for plane in data.planes:
        if plane.name == xplane.HOST_PLANE:
            host = [ev for ev in xplane._events(plane) if ev[0].startswith("bench.")]
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_chips:
            devices[int(m.group(1))] = list(xplane._events(plane, xplane.OPS_LINE))
    lo, hi = next((s, e) for name, s, e in host if name == xplane.WINDOW)
    first_dispatch = min((s for name, s, _ in host if name == "bench.dispatch"), default=lo)
    shift = max(0.0, first_dispatch - min(s for ops in devices.values() for _, s, _ in ops))
    out: dict = defaultdict(float)
    for ops in devices.values():
        clipped = [(name, max(s + shift, lo), min(e + shift, hi))
                   for name, s, e in ops if e + shift > lo and s + shift < hi]
        for name, ns in xplane.self_times(clipped).items():
            out[instruction(name)] += ns / 1e9 / n_chips
    return dict(out)


def split(op_self_s: dict, op_scopes: dict) -> Optional[dict]:
    """Seconds of own device time in each of :data:`LAYERS` and ``other``;
    ``None`` when no instruction carries a layer scope (a program without
    the scopes)."""
    layers = {name: layer_of(op) for name, op in op_scopes.items()}
    if not any(layer != OTHER for layer in layers.values()):
        return None
    out = dict.fromkeys(LAYERS + (OTHER,), 0.0)
    for name, secs in op_self_s.items():
        out[layers.get(name, OTHER)] += secs
    return out


def layer_ms_per_step(ctx, layers: tuple) -> Optional[float]:
    """Milliseconds per step of own device time in ``layers``, for the
    per-layer readers in ``bench/metrics/``. ``None`` until the trace
    summary carries ``op_self_s`` and the cell's facts ``op_scopes``."""
    op_self_s = getattr(ctx.trace, "op_self_s", None)
    op_scopes = ctx.facts.get("op_scopes")
    if op_self_s is None or op_scopes is None or not ctx.window["units"]:
        return None
    secs = split(op_self_s, op_scopes)
    if secs is None:
        return None
    return 1e3 * sum(secs[layer] for layer in layers) / ctx.window["units"]
