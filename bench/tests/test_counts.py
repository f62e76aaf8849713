"""The counts the metrics divide by, against hand counts."""
import jax
import numpy as np
import pytest

import counts
import harness
from tiny import TINY_SIZES

SMOLLM = harness.load_json(harness.BENCH / "configs" / "smollm-360m.json")


def test_matmul_params_hand_count():
    # 2 x (q, o: 64*4*16 each; k, v: 64*2*16 each; MLP 3*64*128) + head 200*64
    assert counts.matmul_params(TINY_SIZES) == 2 * (2 * 4096 + 2 * 2048 + 24576) + 12800
    # smollm: 32 x 9,830,400 + 49,152 * 960 (the tied head)
    assert counts.matmul_params(SMOLLM) == 361_758_720


def test_dense_train_flops_per_token_hand_count():
    # 6 N + 3 passes x 2 products x 2 FLOPs x layers x heads x head_dim x (s+1)/2
    assert counts.dense_train_flops_per_token(TINY_SIZES, 32) == 6 * 86528 + 12 * 2 * 4 * 16 * 33 / 2
    assert counts.dense_train_flops_per_token(SMOLLM, 2048) * 16384 == pytest.approx(4.175e13, rel=1e-3)


def test_dissemination_bytes_per_node():
    # smollm's master tree: 361,821,120 f32; three other nodes' trees
    assert counts.dissemination_bytes_per_node(4, 361_821_120 * 4) == 3 * 1_447_284_480


HLO = """
  %cp.1 = f32[8,128]{1,0} collective-permute(f32[8,128]{1,0} %x), channel_id=1, source_target_pairs={{0,1},{3,2}}
  %cps = (f32[49152,960]{0,1:T(8,128)}, f32[49152,960]{0,1:T(8,128)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%f), channel_id=2, source_target_pairs={{0,2}}, metadata={op_name="x"}
  %cpd = f32[49152,960]{0,1:T(8,128)} collective-permute-done(%cps)
  %ar = f32[8]{0} all-reduce(f32[8]{0} %y), replica_groups={{0,1}}
"""


def test_collective_permute_bytes_hand_count():
    assert counts.collective_permute_bytes(HLO) == 8 * 128 * 4 * 2 + 49152 * 960 * 4


def test_collective_permute_bytes_of_a_compiled_dissemination_round():
    """Four nodes' dissemination moves each node's tree to three others:
    12 transfers of the tree, whatever the plan's order."""
    from jax.sharding import PartitionSpec as P

    from repro.dfl.collectives import GossipPlan, gossip_exchange
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh((4, 1), ("data", "model"))
    plan = GossipPlan.build(mesh, ("data",))
    tree = {"a": jax.ShapeDtypeStruct((4 * 8, 16), np.float32),
            "b": jax.ShapeDtypeStruct((4 * 3,), np.float32)}
    specs = {"a": P("data"), "b": P("data")}
    hlo = jax.jit(lambda t: gossip_exchange("dissemination", plan, mesh, t, specs)
                  ).lower(tree).compile().as_text()
    assert counts.collective_permute_bytes(hlo) == 12 * (8 * 16 + 3) * 4


def test_peaks_table():
    v5e = harness.device_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(SystemExit):
        harness.device_peaks("TPU v9 imaginary")
