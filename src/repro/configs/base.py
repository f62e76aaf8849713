"""Architecture/config system.

``ArchConfig`` fully describes a model family member; ``INPUT_SHAPES`` are
the four assigned workload shapes; ``input_specs`` builds ShapeDtypeStruct
stand-ins for the dry-run (no device allocation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    """One architecture. Every field that shapes parameters lives here."""

    name: str
    family: str  # dense | moe | ssm | hybrid | interleaved | audio | vlm
    source: str  # citation from the assignment table

    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    pad_heads_to: int = 0  # pad Q heads for TP divisibility (dead heads)
    pad_kv_heads_to: int = 0
    head_dim: int = 0  # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab: int = 1024
    max_seq: int = 524_288

    # attention flavour
    attn_free: bool = False  # pure SSM (no attention at all)
    sliding_window: int = 0  # 0 = full attention
    alt_local_global: bool = False  # gemma2: alternate local/global layers
    attn_logit_softcap: float = 0.0  # gemma2: 50.0
    final_logit_softcap: float = 0.0  # gemma2: 30.0
    rope_theta: float = 10_000.0
    use_rope: bool = True  # False: no positional embedding (NoPE)

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25
    moe_dense_residual: bool = False  # arctic: dense MLP in parallel with MoE
    dense_ff: int = 0  # width of the dense residual MLP (arctic)

    # SSM (mamba)
    ssm_state: int = 0
    ssm_version: int = 0  # 1 = mamba1, 2 = mamba2
    d_inner_mult: int = 2
    conv_width: int = 4
    ssm_groups: int = 1  # mamba2: groups of heads sharing one B and C
    ssm_sequential_scan: bool = False  # mamba1: kernel-style scan (vs associative)
    attn_every: int = 0  # hybrid: one attention block every k layers (zamba2)
    shared_attn: bool = False  # zamba2 shares the attention block weights
    # interleaved: one period of the layer stack, "M" a Mamba2 layer and "A"
    # an attention layer, each followed by its own MLP (granite-4.0-h)
    layer_pattern: str = ""

    # muP multipliers (granite): the embedding's output times
    # ``embedding_multiplier``, each residual branch times
    # ``residual_multiplier``, attention scores times ``attention_multiplier``
    # (0: ``head_dim ** -0.5``), logits over ``logits_scaling``
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0

    # modality frontends (STUBS per assignment: precomputed embeddings)
    is_encoder_decoder: bool = False  # whisper
    n_encoder_layers: int = 0
    n_frames: int = 1500  # whisper encoder positions (stub embeddings)
    n_patches: int = 0  # vlm: vision tokens prepended (stub embeddings)

    # numerics / training
    dtype: str = "bfloat16"
    optimizer: str = "adamw"  # adamw | adafactor | momentum
    optimizer_dtype: str = "float32"  # moment dtype; big models use bfloat16
    use_master_fp32: bool = True
    remat: bool = True
    seq_parallel: bool = True  # shard layer-boundary activations over "model"
    microbatches: int = 1  # gradient-accumulation splits of the global batch

    # sharding recipe
    node_axes: Tuple[str, ...] = ("pod", "data")  # mesh axes forming DFL nodes
    expert_axis: str = ""  # mesh axis for expert parallelism ("" = none)

    # which input shapes this arch supports (see DESIGN.md §Arch-applicability)
    skip_shapes: Tuple[str, ...] = ()

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def eff_n_heads(self) -> int:
        return max(self.n_heads, self.pad_heads_to)

    @property
    def eff_n_kv_heads(self) -> int:
        return max(self.n_kv_heads, self.pad_kv_heads_to)

    @property
    def d_inner(self) -> int:
        return self.d_inner_mult * self.d_model

    @property
    def dt_rank(self) -> int:
        return max(1, self.d_model // 16)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        d, hd = self.d_model, self.resolved_head_dim
        total = self.vocab * d  # embedding (tied head unless final softcap arch)
        q = d * self.n_heads * hd
        kv = 2 * d * self.n_kv_heads * hd
        o = self.n_heads * hd * d
        attn = q + kv + o
        mlp = 3 * d * self.d_ff  # gate/up/down
        for layer in range(self.n_layers):
            if self.attn_free:
                total += self._mamba_params()
                continue
            if self.family == "interleaved":
                kind = self.layer_pattern[layer % len(self.layer_pattern)]
                total += (self._mamba2_params() if kind == "M" else attn) + mlp + 2 * d
                continue
            if self.family == "hybrid":
                if self.attn_every and (layer + 1) % self.attn_every == 0:
                    if not (self.shared_attn and layer + 1 > self.attn_every):
                        total += attn + mlp
                else:
                    total += self._mamba2_params()
                continue
            total += attn
            if self.n_experts:
                total += d * self.n_experts  # router
                total += self.n_experts * 3 * d * self.d_ff
                if self.moe_dense_residual:
                    total += 3 * d * (self.dense_ff or self.d_ff)
            else:
                total += mlp
            total += 2 * d  # norms
        if self.is_encoder_decoder:
            enc = self.n_encoder_layers * (attn + mlp)
            dec_cross = self.n_layers * attn  # cross-attention
            total += enc + dec_cross
        return int(total)

    def _mamba_params(self) -> int:
        d, di, n, r = self.d_model, self.d_inner, self.ssm_state, self.dt_rank
        return (
            d * 2 * di  # in_proj
            + di * self.conv_width  # conv
            + di * (r + 2 * n)  # x_proj
            + r * di + di  # dt_proj
            + di * n + di  # A_log, D
            + di * d  # out_proj
        )

    def _mamba2_params(self) -> int:
        d, di = self.d_model, self.d_inner
        nheads = max(1, di // 64)
        conv = di + 2 * self.ssm_groups * self.ssm_state  # x, B and C
        return (d * (di + conv + nheads)  # in_proj: z, xBC, dt
                + conv * (self.conv_width + 1)  # conv and its bias
                + di * d  # out_proj
                + 3 * nheads + di)  # A_log, dt_bias, D; gated norm

    def active_param_count(self) -> int:
        """MoE: params touched per token (6·N_active·D model-FLOPs basis)."""
        if not self.n_experts:
            return self.param_count()
        d = self.d_model
        expert_all = self.n_layers * self.n_experts * 3 * d * self.d_ff
        expert_active = self.n_layers * self.top_k * 3 * d * self.d_ff
        return int(self.param_count() - expert_all + expert_active)

    def replace(self, **kw: Any) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def smoke_variant(self) -> "ArchConfig":
        """Reduced config for CPU smoke tests: 2 layers, d_model<=512, <=4 experts."""
        kw: Dict[str, Any] = dict(
            n_layers=2,
            d_model=256,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads)),
            head_dim=64,
            d_ff=512,
            vocab=512,
            max_seq=4096,
            dtype="float32",
            optimizer_dtype="float32",
            remat=False,
        )
        if self.n_experts:
            kw.update(n_experts=4, top_k=min(2, self.top_k), d_ff=256)
            if self.moe_dense_residual:
                kw.update(dense_ff=256)
        if self.family == "hybrid":
            kw.update(attn_every=2, d_model=256, ssm_state=16)
        if self.attn_free or self.family in ("hybrid", "interleaved"):
            kw.update(ssm_state=16)
        if self.family == "interleaved":
            kw.update(n_layers=len(self.layer_pattern))
        if self.is_encoder_decoder:
            kw.update(n_encoder_layers=2, n_frames=64)
        if self.n_patches:
            kw.update(n_patches=16)
        if self.sliding_window:
            kw.update(sliding_window=128)
        return self.replace(**kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def input_specs(
    arch: ArchConfig, shape: InputShape, dtype: Any = jnp.int32
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input (dry-run pattern).

    * train: tokens + labels, (global_batch, seq)
    * prefill: tokens, (global_batch, seq)
    * decode: one new token per sequence + cache handled by the caller
    * audio/vlm: precomputed frontend embeddings (the assignment's stub)
    """
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind == "train":
        specs["tokens"] = sds((b, s), dtype)
        specs["labels"] = sds((b, s), dtype)
    elif shape.kind == "prefill":
        specs["tokens"] = sds((b, s), dtype)
    else:  # decode: one token against a seq_len cache
        specs["tokens"] = sds((b, 1), dtype)
        specs["cache_positions"] = sds((b,), jnp.int32)
    if arch.family == "audio":
        specs["encoder_frames"] = sds((b, arch.n_frames, arch.d_model), jnp.bfloat16
                                      if arch.dtype == "bfloat16" else jnp.float32)
    if arch.family == "vlm" and shape.kind != "decode":
        specs["patch_embeddings"] = sds(
            (b, arch.n_patches, arch.d_model),
            jnp.bfloat16 if arch.dtype == "bfloat16" else jnp.float32,
        )
    return specs


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch {cfg.name!r}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    _ensure_loaded()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}") from None


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # import all config modules for their registration side effect
    from . import (  # noqa: F401
        arctic_480b,
        falcon_mamba_7b,
        gemma2_2b,
        granite_3_2b,
        granite_4_h_micro,
        paligemma_3b,
        qwen3_moe_30b_a3b,
        smollm_360m,
        stablelm_12b,
        whisper_tiny,
        zamba2_7b,
    )
