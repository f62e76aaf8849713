"""Compile the main path's codec kernels and gossip collectives for a
described TPU v5e (``v5e:2x2``), with no chip attached.

What the chip's compiler refuses (a block shape Mosaic cannot tile, a
primitive with no TPU lowering) fails here, at no chip time. Nothing runs:
these tests say nothing of results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every file.
"""
import math
import re

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


@pytest.fixture
def attention_backend(monkeypatch):
    """Set which attention path ``models.attention`` takes: its backend check
    sees the CPU here, and the compiles below target the described chip."""
    from repro.models import attention as attn_lib
    from repro.models.layers import get_mesh_ctx, set_mesh_ctx

    was = get_mesh_ctx()

    def use(fused: bool, mesh=None, batch_axes=()):
        jax.clear_caches()
        monkeypatch.setattr(attn_lib, "_on_tpu", lambda: fused)
        set_mesh_ctx(mesh, batch_axes)

    yield use
    set_mesh_ctx(*was)
    jax.clear_caches()


def _attention_layer_grad(arch, b, s, sharding_of):
    """``value_and_grad`` of one attention layer of ``arch`` at its widths,
    rematerialised as the model's layers are, compiled; ``sharding_of(rank,
    is_activation)`` places each argument."""
    from repro.configs import get_arch
    from repro.models import attention as attn_lib

    cfg = get_arch(arch)
    params = jax.eval_shape(lambda: attn_lib.init_attention(
        jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
        jnp.bfloat16))
    params = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype,
                                                         sharding=sharding_of(p.ndim, False)),
                          params)
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16, sharding=sharding_of(3, True))

    def loss(p, x_):
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        out = attn_lib.attention(p, x_, pos, rope_theta=cfg.rope_theta,
                                 positions_are_rows=True)
        return jnp.sum(out.astype(jnp.float32))

    return _compile(jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1)), params, x)


def test_fused_attention_step_at_smollm_width(one_chip, attention_backend):
    """The loss gradient of a two-layer smollm-width model at 8 x 2048: the
    fused path puts the kernel's forward, remat forward, dK/dV and dQ in, and
    holds no more temporaries than the scan. (One layer alone holds more,
    726 against 666 MiB: the kernel's backward takes its f32 row statistics
    broadcast to 128 lanes. In the step the f32 logits set the peak.)"""
    from repro.configs import get_arch
    from repro.models import Batch, build_model

    model = build_model(get_arch("smollm-360m").replace(n_layers=2))
    params = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=one_chip)
    batch = Batch(tokens=tok, labels=tok)
    compiled = {}
    for fused in (False, True):
        attention_backend(fused=fused)
        compiled[fused] = _compile(jax.value_and_grad(model.train_loss), params, batch)
    assert "tpu_custom_call" not in compiled[False].as_text()
    assert compiled[True].as_text().count("tpu_custom_call") == 4
    assert (compiled[True].memory_analysis().temp_size_in_bytes
            <= compiled[False].memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("arch,b,s", [("smollm-360m", 8, 2048), ("granite-3-2b", 4, 4096)])
def test_fused_attention_compiles_on_2x2(arch, b, s, topo, attention_backend):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    attention_backend(fused=True, mesh=mesh, batch_axes=("data",))
    hlo = _attention_layer_grad(
        arch, b, s,
        lambda rank, batch: NamedSharding(mesh, P("data") if batch else P())).as_text()
    # forward, remat forward, dK/dV and dQ
    assert hlo.count("tpu_custom_call") == 4


@pytest.mark.parametrize("arch,fused", [("zamba2-7b", True), ("stablelm-12b", False)])
def test_attention_head_dims_compile(arch, fused, one_chip, attention_backend):
    """zamba2's head_dim 112 takes the kernel; stablelm-12b's 160, which the
    kernel refuses once its key tile is shorter than the sequence, keeps the
    scan and still compiles at 2048."""
    attention_backend(fused=True)
    hlo = _attention_layer_grad(arch, 1, 2048, lambda rank, batch: one_chip).as_text()
    assert hlo.count("tpu_custom_call") == (4 if fused else 0)


def test_granite_4_h_cell_step_fits_one_chip(topo, attention_backend):
    """The granite-4.0-h-micro cell's whole DFL step (1 x 8192 tokens, ten
    layers in the published order, 12,544 rows of the vocabulary, AdamW with
    float32 master weights and moments): the compiler's memory fits a v5e
    with room, the attention layer holds the four flash kernels, and the
    chunked SSD never builds a (s, H, P, N) f32 tensor of the whole sequence's
    states: its largest are the masked within-chunk products, (s, H, 256)."""
    from repro.configs import get_arch
    from repro.dfl import DFLConfig, DFLTrainer
    from repro.models import Batch, build_model

    attention_backend(fused=True)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = get_arch("granite-4.0-h-micro-vocab8").replace(n_layers=10)
    trainer = DFLTrainer(build_model(cfg), mesh, DFLConfig(lr=3e-4, warmup=0))
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    batch = Batch(tokens=tok, labels=tok)
    compiled = trainer.jitted_train_step(state, batch).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) <= 15.5 * 2**30
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 4
    f32 = [math.prod(int(d) for d in dims.split(","))
           for dims in re.findall(r"f32\[([\d,]+)\]", hlo)]
    s, heads, head_dim, state_size = 8192, 64, 64, 128
    assert max(f32) < s * heads * head_dim * state_size
    assert max(f32) == s * heads * 256
