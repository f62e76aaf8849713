"""Compile the main path's codec kernels and gossip collectives for a
described TPU v5e (``v5e:2x2``), with no chip attached.

What the chip's compiler refuses (a block shape Mosaic cannot tile, a
primitive with no TPU lowering) fails here, at no chip time. Nothing runs:
these tests say nothing of results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.compress import make_codec


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # a described chip cannot read back what the persistent cache would store
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def flat_size():
    """smollm-360m's flattened parameter count, from shapes alone."""
    from repro.configs import get_arch
    from repro.models import build_model

    shapes = jax.eval_shape(build_model(get_arch("smollm-360m")).init,
                            jax.random.PRNGKey(0))
    return sum(leaf.size for leaf in jax.tree.leaves(shapes))


@pytest.fixture
def kernels_for_tpu(monkeypatch):
    """The codec wrappers pick the compiled kernel by the default backend,
    which is the CPU here; the compiles below target the described chip."""
    from repro.kernels.codec import ops

    jax.clear_caches()  # no trace of the wrappers taken for the CPU is reused
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", ["int8", "int4"])
def test_quantize_dequantize_compile_full_size(name, one_chip, flat_size,
                                               kernels_for_tpu):
    codec = make_codec(name)
    x = jax.ShapeDtypeStruct((flat_size,), jnp.float32, sharding=one_chip)
    enc = _compile(codec.jax_encode, x)
    assert "tpu_custom_call" in enc.as_text()
    codes, scales = enc.out_info
    assert scales.shape == (-(-flat_size // codec.chunk),)
    dec = _compile(lambda e: codec.jax_decode(e, (flat_size,), jnp.float32),
                   (jax.ShapeDtypeStruct(codes.shape, codes.dtype, sharding=one_chip),
                    jax.ShapeDtypeStruct(scales.shape, scales.dtype, sharding=one_chip)))
    assert "tpu_custom_call" in dec.as_text()


def test_topk_select_compiles_full_size(one_chip, flat_size, kernels_for_tpu):
    codec = make_codec("topk")
    x = jax.ShapeDtypeStruct((flat_size,), jnp.float32, sharding=one_chip)
    enc = _compile(codec.jax_encode, x)
    assert "tpu_custom_call" in enc.as_text()
    vals, idx = enc.out_info
    assert vals.shape == idx.shape == (-(-flat_size // codec.block), codec.k)


@pytest.mark.parametrize("mode,codec", [("tree_allreduce", ""),
                                        ("dissemination", "int8")])
def test_gossip_exchange_compiles_on_2x2(mode, codec, topo, kernels_for_tpu):
    from repro.dfl.collectives import GossipPlan, gossip_exchange

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    plan = GossipPlan.build(mesh, ("data",))
    specs = {"w": P("data"), "b": P("data")}
    theta = {k: jax.ShapeDtypeStruct(shape, jnp.float32,
                                     sharding=NamedSharding(mesh, specs[k]))
             for k, shape in (("w", (4, 960, 2560)), ("b", (4, 960)))}
    wire = make_codec(codec) if codec else None
    out = _compile(lambda t: gossip_exchange(mode, plan, mesh, t, specs, codec=wire),
                   theta)
    hlo = out.as_text()
    assert "collective-permute" in hlo
    assert ("tpu_custom_call" in hlo) == bool(codec)
