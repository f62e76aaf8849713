"""The split of device time by the DFL step's layer scopes (``bench/scopes.py``
and its readers in ``bench/metrics/``): on op names worked by hand, and on a
small trace of the tiny DFL step recorded on a TPU v5e by
``bench/record_scopes.py --out bench/testdata``."""
from types import SimpleNamespace

import pytest

import harness
import scopes
import xplane

TRACE = harness.BENCH / "testdata" / "scopes.xplane.pb"
HLO = harness.BENCH / "testdata" / "scopes.hlo.txt"
READERS = ("attn_device_ms", "mlp_device_ms", "head_loss_device_ms", "optimizer_device_ms",
           "other_device_ms")


@pytest.mark.parametrize("op_name, layer", [
    ("jit(step)/dfl.grads/jvp()/while/body/closed_call/attention/dot_general", "attention"),
    ("jit(step)/dfl.grads/transpose(jvp(attention))/mul", "attention"),
    ("jit(step)/dfl.grads/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/jit(silu)/exp", "mlp"),
    # the innermost scope wins
    ("jit(step)/dfl.grads/jvp(lm_head_loss)/attention/add", "attention"),
    # XLA joins the names of what it fused with ';': the first counts
    ("jit(step)/dfl.grads/jvp(mlp)/mul;jit(step)/dfl.grads/jvp(attention)/add", "mlp"),
    ("jit(step)/dfl.clip/sqrt", "dfl.clip"),
    ("jit(step)/dfl.update/mul", "dfl.update"),
    ("jit(step)/dfl.gossip/gossip.step3/collective_permute", "dfl.gossip"),
    # the embedding, norms and anything unscoped are the rest of the step
    ("jit(step)/dfl.grads/jvp(embed)/jit(_take)/gather", "other"),
    ("jit(step)/dfl.grads/jvp()/while/body/closed_call/add", "other"),
    ("jit(step)/dfl.update_extra/mul", "other"),
    ("", "other"),
    (None, "other"),
])
def test_layer_of(op_name, layer):
    assert scopes.layer_of(op_name) == layer


@pytest.mark.parametrize("op_name, phase", [
    ("jit(step)/dfl.grads/jvp()/while/body/closed_call/attention/exp", "forward"),
    ("jit(step)/dfl.grads/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/exp", "remat"),
    ("jit(step)/dfl.grads/transpose(jvp())/while/body/closed_call/checkpoint/attention/mul",
     "backward"),
])
def test_phase_of(op_name, phase):
    assert scopes.phase_of(op_name) == phase


MODULE = """HloModule jit_step, is_scheduled=true

%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %dus_fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused.3
  ROOT %t = (s32[], f32[4]) tuple(%p, %dus_fusion.2), metadata={op_name="jit(step)/dfl.grads/jvp()/while/body/closed_call/attention/add"}
}

%fused.3 (q: f32[4]) -> f32[4] {
  ROOT %q = f32[4]{0} parameter(0)
}

%fused.8 (r: f32[4]) -> f32[4] {
  %r = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%r, %r), metadata={op_name="jit(step)/dfl.update/mul"}
  %s = f32[4]{0} sqrt(%m), metadata={op_name="jit(step)/dfl.update/sqrt"}
  %d = f32[4]{0} dynamic-slice(%s), metadata={op_name="jit(step)/while/body/dynamic_slice"}
  ROOT %c = bf16[4]{0} convert(%d), metadata={op_name="jit(step)/dfl.gossip/convert_element_type"}
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.4 = (s32[], f32[4]) while(%a), condition=%cond.5, body=%body.1, metadata={op_name="jit(step)/dfl.grads/jvp()/while/body/closed_call/attention/closed_call/while"}
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused.3, metadata={op_name="jit(step)/dfl.update/mul;jit(step)/dfl.clip/mul"}
  ROOT %fusion.10 = bf16[4]{0} fusion(%fusion.7), kind=kLoop, calls=%fused.8, metadata={op_name="jit(step)/dfl.gossip/convert_element_type"}
}
"""


def test_op_names_inherit_from_the_caller():
    ops = scopes.op_names(MODULE)
    assert ops["while.4"].endswith("attention/closed_call/while")
    # made by the compiler without a name: it takes its loop's
    assert ops["dus_fusion.2"] == ops["while.4"]
    assert ops["fusion.7"] == "jit(step)/dfl.update/mul"  # the first of the joined names
    assert scopes.layer_of(ops["dus_fusion.2"]) == "attention"


def test_a_fusion_takes_the_layer_of_most_of_its_ops():
    # the optimizer's update fused with the gossip's cast, whose root it is;
    # the unscoped slice does not vote
    assert scopes.op_names(MODULE)["fusion.10"] == "jit(step)/dfl.update/mul"


def test_split_needs_a_scoped_program():
    self_s = {"fusion.1": 2.0, "fusion.2": 1.0, "copy.3": 0.5}
    assert scopes.split(self_s, {"fusion.1": "jit(step)/dfl.grads/jvp()/add"}) is None
    out = scopes.split(self_s, {"fusion.1": "jit(step)/dfl.grads/jvp(attention)/add",
                                "fusion.2": "jit(step)/dfl.update/mul"})
    assert out == {"attention": 2.0, "mlp": 0.0, "lm_head_loss": 0.0, "dfl.clip": 0.0,
                   "dfl.update": 1.0, "dfl.gossip": 0.0, "other": 0.5}


def _ctx(trace, facts, units=4):
    return harness.Context(cell=None, facts=facts, window={"seconds": 1.0, "units": units},
                           peaks={}, trace=trace)


def test_readers_need_both_tables():
    summary = xplane.Summary(window_s=1.0, busy_s=0.9, busy_by_chip=[0.9], device_ops=[],
                             idle_gaps=[])
    for name in READERS:
        read = harness.metric_reader(name)
        assert read(_ctx(None, {})) is None
        assert read(_ctx(summary, {"op_scopes": {}})) is None  # no op_self_s in the summary


def test_recorded_trace():
    """Each layer of the tiny step takes device time, and the five readers
    add up to the busy time per step that ``step_device_ms`` reads."""
    summary = xplane.summarize(TRACE, 1)
    self_s = scopes.window_self_times(TRACE, 1)
    op_scopes = scopes.op_names(HLO.read_text())
    assert set(self_s) <= set(op_scopes)  # every traced op is an instruction of the step
    secs = scopes.split(self_s, op_scopes)
    assert all(secs[layer] > 0 for layer in ("attention", "mlp", "lm_head_loss", "dfl.clip",
                                             "dfl.update", "other"))
    trace = SimpleNamespace(op_self_s=self_s, busy_s=summary.busy_s)
    ctx = _ctx(trace, {"op_scopes": op_scopes, "flops_per_step": 1.0}, units=3)
    values = {name: harness.metric_reader(name)(ctx) for name in READERS}
    assert all(v > 0 for v in values.values())
    step_ms = harness.metric_reader("step_device_ms")(ctx)
    assert sum(values.values()) == pytest.approx(step_ms, rel=0.01)
