"""Payload codec subsystem: wire formats, exact byte accounting, and the
codec-aware executors.

Pins the PR-3 tentpole properties:
  * every codec's ``encode`` produces exactly the bytes its analytic
    ``wire_bytes`` promises (the invariant that makes byte accounting agree
    across executors),
  * decode(encode(x)) respects each codec's deterministic error bound, and
    re-encoding a decoded payload is exact (multi-hop forwarding pays the
    compression error once),
  * the Pallas kernels match their jnp oracles in interpret mode,
  * the queue engine decodes before FedAvg and carries error-feedback
    residuals across rounds, with per-round wire bytes equal to the
    analytic model,
  * plan / engine / netsim (and jax, in a subprocess) report identical
    ``bytes_on_wire`` for a codec scenario, and the int8 paper cell beats
    the fp32 run by >= 2x total round time on the fluid testbed.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.compress import CODEC_NAMES, make_codec, per_send_wire_mb
from repro.core.gossip import GossipEngine, fedavg_numpy
from repro.core.graph import TopologySpec, build_mst, color_graph, make_topology
from repro.core.netsim import TestbedSpec, simulate_policy
from repro.core.plan import (
    DisseminationPolicy,
    SegmentedGossipPolicy,
    make_policy,
    measure_policy,
)
from repro.scenario import ScenarioSpec, run_scenario, scenarios

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RNG = np.random.default_rng(7)


def _tree(sizes=((33, 7), (501,), (4,))):
    return {"layer%d" % i: RNG.normal(size=s).astype(np.float32)
            for i, s in enumerate(sizes)}


def _leaves(tree):
    return [tree[k] for k in sorted(tree)]


class TestWireAccounting:
    @pytest.mark.parametrize("name", CODEC_NAMES)
    def test_encode_matches_analytic_bytes(self, name):
        """encode().bytes_on_wire == sum(wire_bytes(leaf.size)) — exactly."""
        codec = make_codec(name)
        tree = _tree()
        payload, _ = codec.encode(tree, codec.init_state())
        analytic = sum(codec.wire_bytes(l.size) for l in _leaves(tree))
        assert payload.bytes_on_wire == analytic

    @pytest.mark.parametrize("n", [1, 7, 256, 1000, 12345])
    @pytest.mark.parametrize("name", CODEC_NAMES)
    def test_wire_bytes_positive_and_monotone_shapes(self, name, n):
        codec = make_codec(name)
        x = RNG.normal(size=(n,)).astype(np.float32)
        payload, _ = codec.encode({"x": x})
        assert payload.bytes_on_wire == codec.wire_bytes(n) > 0

    def test_identity_wire_mb_is_exact_passthrough(self):
        # fp32 accounting must be bit-identical to the pre-codec pipeline
        assert make_codec("fp32").wire_mb(21.2) == 21.2
        assert per_send_wire_mb(None, 21.2, 0.25) == 21.2 * 0.25

    def test_compression_ratios(self):
        n = 1 << 16
        assert make_codec("bf16").ratio(n) == 0.5
        assert make_codec("int8").ratio(n) == pytest.approx(0.25, abs=0.01)
        assert make_codec("int4").ratio(n) == pytest.approx(0.125, abs=0.01)
        topk = make_codec("topk")  # 5% density at 8 B/entry ~ 10x
        assert topk.ratio(n) == pytest.approx(
            8 * topk.k / (4 * topk.block), rel=1e-6)

    def test_unknown_codec_raises(self):
        with pytest.raises(ValueError, match="unknown codec"):
            make_codec("zstd")


class TestRoundTrip:
    def test_identity_exact(self):
        codec = make_codec("fp32")
        tree = _tree()
        out, _ = codec.roundtrip(tree)
        for k in tree:
            np.testing.assert_array_equal(out[k], tree[k])

    @pytest.mark.parametrize("name", ["bf16", "int8", "int4"])
    def test_error_within_declared_bound(self, name):
        codec = make_codec(name)
        tree = _tree()
        out, _ = codec.roundtrip(tree)
        for k in tree:
            bound = codec.mean_atol(float(np.abs(tree[k]).max()))
            assert float(np.abs(out[k] - tree[k]).max()) <= bound

    @pytest.mark.parametrize("name", ["bf16", "int8", "int4", "topk"])
    def test_reencode_of_decoded_is_exact(self, name):
        """Multi-hop forwarding: hop 2..N must not add error."""
        codec = make_codec(name)
        d1, _ = codec.roundtrip(_tree())
        d2, _ = codec.roundtrip(d1)
        for k in d1:
            np.testing.assert_array_equal(d1[k], d2[k])

    def test_topk_sparsity_and_residual_identity(self):
        codec = make_codec("topk", fraction=0.1, block=50)
        x = {"w": RNG.normal(size=(600,)).astype(np.float32)}
        payload, state = codec.encode(x, codec.init_state())
        dec = codec.decode(payload)
        # exactly k kept per full block
        blocks = dec["w"][:600 // 50 * 50].reshape(-1, 50)
        assert (np.count_nonzero(blocks, axis=1) <= codec.k).all()
        # what was dropped is exactly the residual
        np.testing.assert_allclose(dec["w"] + state["w"], x["w"], atol=0)

    def test_topk_error_feedback_transmits_everything_eventually(self):
        """EF-SGD property: the running mean of decoded payloads converges to
        the true tensor even at 10% density."""
        codec = make_codec("topk", fraction=0.1, block=64)
        x = {"w": RNG.normal(size=(512,)).astype(np.float32)}
        state = codec.init_state()
        acc = np.zeros(512, np.float32)
        rounds = 40
        for _ in range(rounds):
            payload, state = codec.encode(x, state)
            acc += codec.decode(payload)["w"]
        err = np.abs(acc / rounds - x["w"]).max()
        assert err < 0.35 * np.abs(x["w"]).max()  # one-shot topk would be ~1x


class TestKernels:
    """Pallas kernels vs their jnp oracles, interpret mode (CPU CI)."""

    @pytest.mark.parametrize("c,chunk", [(3, 128), (10, 256), (1, 512)])
    def test_quantize_matches_ref(self, c, chunk):
        import jax.numpy as jnp

        from repro.kernels.codec.quant_pack import dequantize_chunks, quantize_chunks
        from repro.kernels.codec.ref import dequantize_ref, quantize_ref

        x = jnp.asarray(RNG.normal(size=(c, chunk)).astype(np.float32))
        codes, scales = quantize_chunks(x, qmax=127.0, interpret=True)
        cr, sr = quantize_ref(x, 127.0)
        np.testing.assert_array_equal(np.asarray(codes), np.asarray(cr))
        np.testing.assert_allclose(np.asarray(scales), np.asarray(sr), rtol=1e-6)
        out = dequantize_chunks(codes, scales, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dequantize_ref(cr, sr)), rtol=1e-6)

    @pytest.mark.parametrize("c,block,k", [(4, 64, 5), (9, 128, 1), (2, 32, 32)])
    def test_topk_kernel_matches_ref(self, c, block, k):
        import jax.numpy as jnp

        from repro.kernels.codec.ref import topk_select_ref
        from repro.kernels.codec.topk_pack import topk_select_blocks

        x = jnp.asarray(RNG.normal(size=(c, block)).astype(np.float32))
        vals, idx = topk_select_blocks(x, k=k, interpret=True)
        vr, ir = topk_select_ref(x, k)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), rtol=1e-6)

    def test_int4_ops_pack_roundtrip(self):
        from repro.kernels.codec.ops import dequantize_op, quantize_op

        x = RNG.normal(size=(777,)).astype(np.float32)
        codes, scales = quantize_op(x, bits=4, chunk=128)
        assert codes.dtype == np.uint8 and codes.shape[1] == 64  # 2 codes/byte
        dec = np.asarray(dequantize_op(codes, scales, size=777, bits=4, chunk=128))
        bound = make_codec("int4", chunk=128).mean_atol(float(np.abs(x).max()))
        assert np.abs(dec - x).max() <= bound

    def test_jax_and_numpy_codecs_agree(self):
        """The two implementations of each wire format are the same format."""
        import jax.numpy as jnp

        x = RNG.normal(size=(37, 19)).astype(np.float32)
        for name in ("bf16", "int8", "int4", "topk"):
            codec = make_codec(name)
            via_jax = np.asarray(codec.jax_roundtrip(jnp.asarray(x)))
            via_np = codec.decode(codec.encode({"x": x})[0])["x"]
            np.testing.assert_allclose(via_jax, via_np, atol=1e-6)


class TestEngineCodec:
    def _setup(self, n=6, seed=3):
        g = make_topology(TopologySpec(kind="erdos_renyi", n=n, seed=seed))
        mst = build_mst(g)
        return mst, color_graph(mst)

    def test_aggregate_decodes_before_fedavg(self):
        mst, colors = self._setup()
        payloads = [{"w": RNG.normal(size=(64,)).astype(np.float32)}
                    for _ in range(6)]
        codec = make_codec("int8")
        eng = GossipEngine(policy=DisseminationPolicy(mst, colors), codec=codec)
        eng.run_round(0, payloads)
        agg = eng.aggregate(fedavg_numpy)
        true_mean = np.mean([p["w"] for p in payloads], axis=0)
        bound = max(codec.mean_atol(float(np.abs(p["w"]).max()))
                    for p in payloads)
        for node_agg in agg:
            assert np.abs(node_agg["w"] - true_mean).max() <= bound

    def test_round_wire_bytes_match_analytic(self):
        mst, colors = self._setup()
        payloads = [{"w": RNG.normal(size=(100,)).astype(np.float32)}
                    for _ in range(6)]
        codec = make_codec("int8")
        eng = GossipEngine(policy=DisseminationPolicy(mst, colors), codec=codec)
        eng.run_round(0, payloads)
        attempted = sum(len(r.sends) + len(r.dropped) for r in eng.reports)
        assert eng.round_wire_bytes == attempted * codec.wire_bytes(100)

    def test_error_feedback_persists_across_rounds(self):
        mst, colors = self._setup()
        payloads = [{"w": RNG.normal(size=(80,)).astype(np.float32)}
                    for _ in range(6)]
        codec = make_codec("topk", fraction=0.25, block=16)
        eng = GossipEngine(policy=DisseminationPolicy(mst, colors), codec=codec)
        eng.run_round(0, payloads)
        states_r0 = {pid: st["w"].copy() for pid, st in eng._ef_states.items()}
        assert len(states_r0) == 6 and any(
            np.abs(st).max() > 0 for st in states_r0.values())
        eng.run_round(1, payloads)
        # round 1 encoded (payload + round-0 residual): residuals evolved
        assert any(np.abs(eng._ef_states[pid]["w"] - states_r0[pid]).max() > 0
                   for pid in states_r0)
        # and the EF-compensated payload decodes closer to the truth than the
        # EF-free one would round after round (aggregate stays within ~bound)
        agg = eng.aggregate(fedavg_numpy)
        assert np.isfinite(agg[0]["w"]).all()

    def test_segmented_engine_encodes_per_segment(self):
        mst, colors = self._setup()
        S = 4
        payloads = [[{"w": RNG.normal(size=(16,)).astype(np.float32)}
                     for _ in range(S)] for _ in range(6)]
        codec = make_codec("int8", chunk=16)
        eng = GossipEngine(policy=SegmentedGossipPolicy(mst, colors, segments=S),
                           codec=codec)
        eng.run_round(0, payloads)
        agg = eng.aggregate(fedavg_numpy)
        assert len(agg[0]) == S  # one aggregate per segment
        true_seg0 = np.mean([p[0]["w"] for p in payloads], axis=0)
        assert np.abs(agg[0][0]["w"] - true_seg0).max() < 0.05


class TestNetsimCodec:
    def test_flow_sizes_use_codec_wire_bytes(self):
        g = make_topology(TopologySpec(kind="erdos_renyi", n=6, seed=3))
        pol = make_policy("mosgu", g)
        codec = make_codec("int8")
        res = simulate_policy(make_policy("mosgu", g), TestbedSpec(n=6), 21.2,
                              codec=codec)
        expected = res.n_transfers * per_send_wire_mb(codec, 21.2)
        assert res.bytes_on_wire_mb == pytest.approx(expected)
        # and matches the counting path exactly
        stats = measure_policy(pol, model_bytes=21.2e6, codec=codec)
        assert res.bytes_on_wire_mb * 1e6 == pytest.approx(stats["wire_bytes"])

    def test_fp32_codec_keeps_legacy_results(self):
        """codec=None and codec='fp32' are byte- and time-identical."""
        spec = scenarios.get("paper_table3")
        a = run_scenario(spec, executor="netsim")
        b = run_scenario(spec.replace(codec="fp32"), executor="netsim")
        assert a.total_time_s == b.total_time_s
        assert a.total_bytes_on_wire_mb == b.total_bytes_on_wire_mb
        assert a.total_bytes_on_wire_mb == pytest.approx(a.total_bytes_mb)


class TestScenarioCodec:
    def test_registry_has_codec_scenarios(self):
        assert {"quantized_table3", "topk_sweep"} <= set(scenarios.names())
        assert scenarios.get("quantized_table3").codec == "int8"
        assert scenarios.get("topk_sweep").codec == "topk"

    @pytest.mark.parametrize("name", ["quantized_table3", "topk_sweep"])
    def test_cross_executor_bytes_on_wire_agree(self, name):
        """The acceptance invariant: plan/engine/netsim report identical
        per-round delivered wire bytes under a codec."""
        spec = scenarios.get(name)
        results = {e: run_scenario(spec, executor=e)
                   for e in ("plan", "engine", "netsim")}
        per_round = {e: [pytest.approx(r.bytes_on_wire_mb) for r in res.rounds]
                     for e, res in results.items()}
        assert ([r.bytes_on_wire_mb for r in results["plan"].rounds]
                == per_round["engine"] == per_round["netsim"])
        # and compression really compressed
        for res in results.values():
            assert res.total_bytes_on_wire_mb < 0.3 * res.total_bytes_mb

    def test_int8_halves_paper_table3_round_time(self):
        """Acceptance: >= 2x total-round-time win for int8 on the paper cell."""
        fp32 = run_scenario(scenarios.get("paper_table3"), executor="netsim")
        int8 = run_scenario(scenarios.get("quantized_table3"), executor="netsim")
        assert int8.total_transmissions == fp32.total_transmissions
        assert fp32.total_time_s >= 2.0 * int8.total_time_s

    def test_unknown_codec_rejected(self):
        with pytest.raises(ValueError, match="unknown codec"):
            ScenarioSpec(codec="gzip").validate()

    def test_codec_serializes(self):
        res = run_scenario(scenarios.get("quantized_table3"), executor="plan")
        d = res.to_dict()
        assert d["spec"]["codec"] == "int8"
        assert d["totals"]["bytes_on_wire_mb"] < d["totals"]["bytes_mb"]
        assert all("bytes_on_wire_mb" in r for r in d["rounds_detail"])

    def test_codec_with_churn_and_drops(self):
        """Codec accounting composes with the rest of the scenario axes."""
        spec = scenarios.get("churn_storm").replace(codec="int4")
        res = run_scenario(spec, executor="engine")
        assert res.total_bytes_on_wire_mb < 0.2 * res.total_bytes_mb
        assert len(res.rounds) == spec.rounds


class TestJaxCodec:
    def test_jax_executor_matches_plan_bytes_and_numerics(self):
        """quantized ppermute collectives: same wire accounting as the
        counting executor, numerics within the codec's bound; topk skips the
        exactness check (numerics_ok None)."""
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        code = textwrap.dedent("""
            from repro.core.graph import TopologySpec
            from repro.scenario import ScenarioSpec, run_scenario
            spec = ScenarioSpec(
                name="jax-codec", overlay=TopologySpec(kind="complete", n=4, seed=0),
                protocol="mosgu", payload=2.0, codec="int8")
            jx = run_scenario(spec, executor="jax")
            pl = run_scenario(spec, executor="plan")
            wire_match = ([round(r.bytes_on_wire_mb, 9) for r in jx.rounds]
                          == [round(r.bytes_on_wire_mb, 9) for r in pl.rounds])
            tk = run_scenario(spec.replace(codec="topk"), executor="jax")
            print("OK", all(r.numerics_ok for r in jx.rounds), wire_match,
                  all(r.numerics_ok is None for r in tk.rounds),
                  jx.rounds[0].bytes_on_wire_mb < 0.3 * jx.rounds[0].bytes_mb)
        """)
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, env=env, timeout=520)
        assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
        assert out.stdout.strip() == "OK True True True True"

    def test_error_feedback_training_smoke_converges(self):
        """The acceptance smoke: DFL training with error-feedback top-k
        gossip still learns (loss decreasing over the horizon)."""
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        code = textwrap.dedent("""
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh((2, 2, 2), ("pod", "data", "model"))
            from repro.configs import get_arch
            from repro.models import Batch, build_model
            from repro.dfl import DFLConfig, DFLTrainer
            from repro.data import DataConfig, FederatedData
            cfg = get_arch("smollm-360m").smoke_variant()
            model = build_model(cfg)
            tr = DFLTrainer(model, mesh, DFLConfig(
                gossip_mode="dissemination", codec="topk", lr=2e-3))
            state = tr.init_state(jax.random.PRNGKey(0))
            assert "codec_ef" in state.opt_state
            data = FederatedData(DataConfig(vocab=cfg.vocab, seq_len=64,
                                            batch_per_node=2, n_nodes=4))
            tok, lab = data.global_batch()
            batch = Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
            step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                        jax.eval_shape(lambda: batch))
            losses = []
            for i in range(14):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
                tok, lab = data.global_batch()
                batch = Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
            ef_live = any(float(jnp.abs(l).max()) > 0
                          for l in jax.tree.leaves(state.opt_state["codec_ef"]))
            print("LOSSES", losses[0], min(losses[-3:]), ef_live)
        """)
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, env=env, timeout=520)
        assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
        first, last, ef_live = out.stdout.strip().split()[-3:]
        assert float(last) < float(first)
        assert ef_live == "True"
