"""Unified model builder: ArchConfig -> init / forward / train_loss / decode.

Every family lowers through scan-over-layers with stacked parameters (small
HLO, fast 512-device compiles) and optional per-layer remat for training.

Families
  dense   : llama-style GQA decoder (smollm, granite, stablelm), gemma2
            (alternating local/global + softcaps, scanned in layer *pairs*),
            and the long-context sliding-window variant of any dense arch
  moe     : dense attention + top-k expert MLP (arctic adds a dense residual)
  ssm     : attention-free Mamba1 stack (falcon-mamba)
  hybrid  : Mamba2 blocks with a shared attention block every k layers (zamba2)
  audio   : whisper enc-dec backbone (frame embeddings stubbed upstream)
  vlm     : paligemma — gemma decoder over [patch embeddings; text], prefix
            attends bidirectionally, suffix causally
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..obs import scopes
from . import attention as attn_lib
from . import mamba as mamba_lib
from .layers import (
    Params,
    cross_entropy_loss,
    dense_init,
    embed,
    init_embedding,
    init_mlp,
    logits_from_embedding,
    mlp,
    rms_norm,
)
from .moe import init_moe, moe_layer

MOE_AUX_WEIGHT = 0.01


@jax.tree_util.register_dataclass
@dataclass
class Batch:
    tokens: jax.Array
    labels: Optional[jax.Array] = None
    encoder_frames: Optional[jax.Array] = None
    patch_embeddings: Optional[jax.Array] = None


class Model:
    """Functional model; all state lives in explicit params/cache pytrees."""

    def __init__(self, cfg: ArchConfig, long_context: bool = False):
        self.cfg = cfg
        self.long_context = long_context
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.act_sharding = None  # set by set_mesh_context (sequence parallelism)
        self.expert_sharding = None  # (mesh, axis) for MoE dispatch constraints
        if cfg.family == "hybrid":
            self.n_super = cfg.n_layers // cfg.attn_every
            self.mamba_per_super = cfg.attn_every - 1
            self.n_tail = cfg.n_layers - self.n_super * cfg.attn_every
        if cfg.alt_local_global:
            assert cfg.n_layers % 2 == 0

    def set_mesh_context(self, mesh, batch_axes: Tuple[str, ...]) -> None:
        """Enable sequence-parallel activation sharding between layers.

        Layer-scan carries are the dominant train-memory term (one (b, s, d)
        activation saved per layer for backward); sharding the sequence dim
        over "model" divides that by the TP width (Korthikanti-style
        sequence parallelism) — GSPMD inserts the gather/scatter pairs.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .layers import set_mesh_ctx

        set_mesh_ctx(mesh, tuple(batch_axes))
        if mesh is not None and self.cfg.expert_axis in mesh.shape:
            self.expert_sharding = (mesh, self.cfg.expert_axis, tuple(batch_axes))
        if (mesh is None or "model" not in mesh.shape or mesh.shape["model"] == 1
                or not self.cfg.seq_parallel):
            self.act_sharding = None
            return
        self.act_sharding = NamedSharding(
            mesh, P(batch_axes if batch_axes else None, "model", None)
        )

    def _shard_divisor(self) -> int:
        """Device count dividing per-chip scan intermediates (batch x model)."""
        ns = self.act_sharding
        if ns is None:
            return 1
        div = ns.mesh.shape["model"]
        b_axes = ns.spec[0]
        if b_axes:
            for a in (b_axes if isinstance(b_axes, tuple) else (b_axes,)):
                div *= ns.mesh.shape[a]
        return div

    def _shard_acts(self, x: jax.Array) -> jax.Array:
        ns = self.act_sharding
        if ns is None or x.ndim != 3:
            return x
        b_axes, s_axis = ns.spec[0], ns.spec[1]
        mesh = ns.mesh
        n_b = 1
        if b_axes:
            for a in (b_axes if isinstance(b_axes, tuple) else (b_axes,)):
                n_b *= mesh.shape[a]
        if x.shape[0] % max(n_b, 1) or x.shape[1] % mesh.shape[s_axis]:
            return x
        return jax.lax.with_sharding_constraint(x, ns)

    # -- window policy -------------------------------------------------------
    def layer_window(self, local: bool) -> int:
        """Effective sliding window for a layer (0 = full attention)."""
        cfg = self.cfg
        if cfg.alt_local_global:
            return cfg.sliding_window if local else 0
        if self.long_context and cfg.sliding_window:
            return cfg.sliding_window  # long-context variant: all layers windowed
        return 0  # standard variant: full attention

    # ======================================================================
    # init
    # ======================================================================
    def init(self, key: jax.Array) -> Params:
        cfg, dt = self.cfg, self.dtype
        keys = jax.random.split(key, 8)
        params: Params = {"embed": init_embedding(keys[0], cfg.vocab, cfg.d_model, dt)}
        params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)

        def init_attn(k):
            return attn_lib.init_attention(
                k, cfg.d_model, cfg.eff_n_heads, cfg.eff_n_kv_heads,
                cfg.resolved_head_dim, dt
            )

        def init_dense_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "attn": init_attn(k1),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dt),
            }

        def init_moe_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "attn": init_attn(k1),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "moe": init_moe(k2, cfg.d_model, cfg.d_ff, cfg.n_experts, dt,
                                cfg.dense_ff if cfg.moe_dense_residual else 0),
            }

        def init_mamba_block(k):
            if cfg.ssm_version == 2:
                body = mamba_lib.init_mamba2(k, cfg.d_model, cfg.d_inner, cfg.ssm_state,
                                             cfg.conv_width, dt)
            else:
                body = mamba_lib.init_mamba1(k, cfg.d_model, cfg.d_inner, cfg.ssm_state,
                                             cfg.dt_rank, cfg.conv_width, dt)
            return {"ln": jnp.zeros((cfg.d_model,), jnp.float32), "body": body}

        fam = cfg.family
        if fam in ("dense", "vlm"):
            n = cfg.n_layers
            if cfg.alt_local_global:
                lk = jax.random.split(keys[1], n // 2)
                gk = jax.random.split(keys[2], n // 2)
                params["local_blocks"] = jax.vmap(init_dense_block)(lk)
                params["global_blocks"] = jax.vmap(init_dense_block)(gk)
            else:
                params["blocks"] = jax.vmap(init_dense_block)(jax.random.split(keys[1], n))
        elif fam == "moe":
            params["blocks"] = jax.vmap(init_moe_block)(jax.random.split(keys[1], cfg.n_layers))
        elif fam == "ssm":
            params["blocks"] = jax.vmap(init_mamba_block)(jax.random.split(keys[1], cfg.n_layers))
        elif fam == "hybrid":
            mk = jax.random.split(keys[1], self.n_super * self.mamba_per_super)
            stacked = jax.vmap(init_mamba_block)(mk)
            params["mamba_blocks"] = jax.tree.map(
                lambda a: a.reshape(self.n_super, self.mamba_per_super, *a.shape[1:]), stacked
            )
            params["shared_attn"] = init_dense_block(keys[2])  # shared weights (zamba2)
            if self.n_tail:
                params["tail_blocks"] = jax.vmap(init_mamba_block)(
                    jax.random.split(keys[3], self.n_tail)
                )
        elif fam == "audio":
            params["enc_blocks"] = jax.vmap(init_dense_block)(
                jax.random.split(keys[1], cfg.n_encoder_layers)
            )
            params["enc_final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)

            def init_dec_block(k):
                k1, k2, k3 = jax.random.split(k, 3)
                return {
                    "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                    "attn": init_attn(k1),
                    "ln_cross": jnp.zeros((cfg.d_model,), jnp.float32),
                    "cross": init_attn(k2),
                    "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                    "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dt),
                }

            params["blocks"] = jax.vmap(init_dec_block)(jax.random.split(keys[2], cfg.n_layers))
        else:
            raise ValueError(f"unknown family {fam}")
        return params

    # ======================================================================
    # full-sequence forward (train / prefill)
    # ======================================================================
    def _maybe_remat(self, fn):
        return jax.checkpoint(fn) if self.cfg.remat else fn

    def _attn_kwargs(self, window: int) -> Dict[str, Any]:
        return dict(
            sliding_window=window,
            softcap=self.cfg.attn_logit_softcap,
            rope_theta=self.cfg.rope_theta,
        )

    def _dense_body(self, window: int, prefix_len: int = 0):
        def body(carry, block):
            x, positions = carry
            h = attn_lib.attention(
                block["attn"], rms_norm(x, block["ln1"]), positions,
                causal=True, prefix_len=prefix_len, **self._attn_kwargs(window),
            )
            x = x + h
            x = x + mlp(block["mlp"], rms_norm(x, block["ln2"]))
            return (self._shard_acts(x), positions), None

        return self._maybe_remat(body)

    def forward(self, params: Params, batch: Batch) -> Tuple[jax.Array, jax.Array]:
        """Returns (logits over full sequence, moe_aux_loss)."""
        cfg = self.cfg
        fam = cfg.family
        aux = jnp.zeros((), jnp.float32)

        if fam == "audio":
            return self._forward_encdec(params, batch), aux

        tokens = batch.tokens
        x = embed(params["embed"], tokens).astype(self.dtype)
        prefix_len = 0
        if fam == "vlm" and batch.patch_embeddings is not None:
            x = jnp.concatenate([batch.patch_embeddings.astype(self.dtype), x], axis=1)
            prefix_len = batch.patch_embeddings.shape[1]
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        if fam in ("dense", "vlm"):
            if cfg.alt_local_global:
                def pair_body(carry, blocks):
                    lb, gb = blocks
                    carry, _ = self._dense_body(cfg.sliding_window)(carry, lb)
                    carry, _ = self._dense_body(0)(carry, gb)
                    return carry, None

                (x, _), _ = jax.lax.scan(
                    pair_body, (x, positions),
                    (params["local_blocks"], params["global_blocks"]),
                )
            else:
                window = self.layer_window(local=True) if self.long_context else 0
                (x, _), _ = jax.lax.scan(
                    self._dense_body(window, prefix_len), (x, positions), params["blocks"]
                )
        elif fam == "moe":
            window = cfg.sliding_window if self.long_context else 0

            def body(carry, block):
                x, positions, aux = carry
                h = attn_lib.attention(
                    block["attn"], rms_norm(x, block["ln1"]), positions,
                    causal=True, **self._attn_kwargs(window),
                )
                x = x + h
                y, a = moe_layer(block["moe"], rms_norm(x, block["ln2"]), cfg.top_k,
                                 capacity_factor=cfg.moe_capacity_factor,
                                 expert_sharding=self.expert_sharding)
                return (self._shard_acts(x + y), positions, aux + a), None

            (x, _, aux), _ = jax.lax.scan(
                self._maybe_remat(body), (x, positions, aux), params["blocks"]
            )
            aux = aux / cfg.n_layers
        elif fam == "ssm":
            chunk = mamba_lib.pick_chunk(
                b, cfg.d_inner * cfg.ssm_state, 256 << 20 if self.act_sharding is None
                else (256 << 20) * self._shard_divisor())

            def body(x, block):
                y = mamba_lib.mamba1_forward(
                    block["body"], rms_norm(x, block["ln"]), cfg.ssm_state,
                    cfg.dt_rank, chunk, sequential=cfg.ssm_sequential_scan,
                )
                return self._shard_acts(x + y), None

            x, _ = jax.lax.scan(self._maybe_remat(body), x, params["blocks"])
        elif fam == "hybrid":
            x = self._forward_hybrid(params, x, positions)
        else:
            raise ValueError(fam)

        with jax.named_scope(scopes.LM_HEAD_LOSS):
            x = rms_norm(x, params["final_norm"])
            logits = logits_from_embedding(params["embed"], x, cfg.vocab,
                                           cfg.final_logit_softcap)
            if fam == "vlm" and prefix_len:
                logits = logits[:, prefix_len:]
        return logits, aux

    def _forward_hybrid(self, params: Params, x: jax.Array, positions: jax.Array) -> jax.Array:
        cfg = self.cfg
        b = x.shape[0]
        chunk = mamba_lib.pick_chunk(
            b, (cfg.d_inner // 64) * 64 * cfg.ssm_state,
            256 << 20 if self.act_sharding is None
            else (256 << 20) * self._shard_divisor())

        def mamba_step(x, block):
            y = mamba_lib.mamba2_forward(block["body"], rms_norm(x, block["ln"]),
                                         cfg.ssm_state, chunk=chunk,
                                         sequential=cfg.ssm_sequential_scan)
            return self._shard_acts(x + y)

        shared = params["shared_attn"]

        def super_body(carry, mblocks):
            x, positions = carry

            def inner(x, blk):
                return mamba_step(x, blk), None

            x, _ = jax.lax.scan(inner, x, mblocks)
            # shared attention block (weights reused across super-blocks)
            h = attn_lib.attention(shared["attn"], rms_norm(x, shared["ln1"]), positions,
                                   causal=True, **self._attn_kwargs(0))
            x = x + h
            x = x + mlp(shared["mlp"], rms_norm(x, shared["ln2"]))
            return (self._shard_acts(x), positions), None

        (x, _), _ = jax.lax.scan(self._maybe_remat(super_body), (x, positions),
                                 params["mamba_blocks"])
        if self.n_tail:
            def tail(x, blk):
                return mamba_step(x, blk), None

            x, _ = jax.lax.scan(self._maybe_remat(tail), x, params["tail_blocks"])
        return x

    def _forward_encdec(self, params: Params, batch: Batch) -> jax.Array:
        cfg = self.cfg
        frames = batch.encoder_frames.astype(self.dtype)
        b, f, _ = frames.shape
        fpos = jnp.broadcast_to(jnp.arange(f), (b, f))

        def enc_body(carry, block):
            x, fpos = carry
            h = attn_lib.attention(block["attn"], rms_norm(x, block["ln1"]), fpos,
                                   causal=False, rope_theta=cfg.rope_theta)
            x = x + h
            x = x + mlp(block["mlp"], rms_norm(x, block["ln2"]))
            return (self._shard_acts(x), fpos), None

        (enc, _), _ = jax.lax.scan(self._maybe_remat(enc_body), (frames, fpos),
                                   params["enc_blocks"])
        enc = rms_norm(enc, params["enc_final_norm"])

        x = embed(params["embed"], batch.tokens).astype(self.dtype)
        s = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        def dec_body(carry, block):
            x, positions = carry
            h = attn_lib.attention(block["attn"], rms_norm(x, block["ln1"]), positions,
                                   causal=True, rope_theta=cfg.rope_theta)
            x = x + h
            # cross attention: K/V from encoder output, no rope
            kc = jnp.einsum("bsd,dhk->bshk", enc, block["cross"]["wk"])
            vc = jnp.einsum("bsd,dhk->bshk", enc, block["cross"]["wv"])
            h = attn_lib.attention(block["cross"], rms_norm(x, block["ln_cross"]), positions,
                                   causal=False, use_rope=False, kv_override=(kc, vc),
                                   kv_positions=None)
            x = x + h
            x = x + mlp(block["mlp"], rms_norm(x, block["ln2"]))
            return (x, positions), None

        (x, _), _ = jax.lax.scan(self._maybe_remat(dec_body), (x, positions), params["blocks"])
        with jax.named_scope(scopes.LM_HEAD_LOSS):
            x = rms_norm(x, params["final_norm"])
            return logits_from_embedding(params["embed"], x, cfg.vocab)

    # ======================================================================
    # losses
    # ======================================================================
    def train_loss(self, params: Params, batch: Batch) -> jax.Array:
        logits, aux = self.forward(params, batch)
        with jax.named_scope(scopes.LM_HEAD_LOSS):
            return cross_entropy_loss(logits, batch.labels) + MOE_AUX_WEIGHT * aux

    # ======================================================================
    # decode: cache + one-token step
    # ======================================================================
    def init_cache(self, batch: int, cache_len: int) -> Params:
        cfg, dt = self.cfg, self.dtype
        hd, kv = cfg.resolved_head_dim, cfg.eff_n_kv_heads
        fam = cfg.family

        def kvc(n_layers: int, length: int) -> Params:
            return {
                "k": jnp.zeros((n_layers, batch, length, kv, hd), dt),
                "v": jnp.zeros((n_layers, batch, length, kv, hd), dt),
            }

        def ring(length: int) -> int:
            return min(length, cfg.sliding_window) if cfg.sliding_window else length

        if fam in ("dense", "vlm"):
            if cfg.alt_local_global:
                return {
                    "local": kvc(cfg.n_layers // 2, ring(cache_len)),
                    "global": kvc(cfg.n_layers // 2, cache_len),
                }
            length = ring(cache_len) if self.long_context else cache_len
            return {"kv": kvc(cfg.n_layers, length)}
        if fam == "moe":
            length = ring(cache_len) if self.long_context else cache_len
            return {"kv": kvc(cfg.n_layers, length)}
        if fam == "ssm":
            c = mamba_lib.init_mamba1_cache(batch, cfg.d_inner, cfg.ssm_state, cfg.conv_width, dt)
            return {"mamba": jax.tree.map(
                lambda a: jnp.zeros((cfg.n_layers, *a.shape), a.dtype), c)}
        if fam == "hybrid":
            c = mamba_lib.init_mamba2_cache(batch, cfg.d_inner, cfg.ssm_state, cfg.conv_width, dt)
            out = {
                "mamba": jax.tree.map(
                    lambda a: jnp.zeros((self.n_super, self.mamba_per_super, *a.shape), a.dtype), c),
                "attn": kvc(self.n_super, cache_len),
            }
            if self.n_tail:
                out["tail"] = jax.tree.map(
                    lambda a: jnp.zeros((self.n_tail, *a.shape), a.dtype), c)
            return out
        if fam == "audio":
            return {
                "kv": kvc(cfg.n_layers, cache_len),
                "cross_k": jnp.zeros((cfg.n_layers, batch, cfg.n_frames, kv, hd), dt),
                "cross_v": jnp.zeros((cfg.n_layers, batch, cfg.n_frames, kv, hd), dt),
            }
        raise ValueError(fam)

    def decode_step(
        self, params: Params, tokens: jax.Array, positions: jax.Array, cache: Params
    ) -> Tuple[jax.Array, Params]:
        """tokens: (b, 1); positions: (b,) absolute index of the new token."""
        cfg = self.cfg
        fam = cfg.family
        x = embed(params["embed"], tokens).astype(self.dtype)
        kw = dict(softcap=cfg.attn_logit_softcap, rope_theta=cfg.rope_theta)

        def attn_decode(block, x, c, window):
            h, c2 = attn_lib.decode_attention(
                block["attn"], rms_norm(x, block["ln1"]), positions, c,
                sliding_window=window, **kw)
            x = x + h
            return x, c2

        new_cache: Params = {}
        if fam in ("dense", "vlm", "moe"):
            window = cfg.sliding_window if (self.long_context or cfg.alt_local_global) else 0
            if cfg.alt_local_global:
                def pair(x, xs):
                    lb, gb, lc, gc = xs
                    x, lc2 = attn_decode(lb, x, lc, cfg.sliding_window)
                    x = x + mlp(lb["mlp"], rms_norm(x, lb["ln2"]))
                    x, gc2 = attn_decode(gb, x, gc, 0)
                    x = x + mlp(gb["mlp"], rms_norm(x, gb["ln2"]))
                    return x, (lc2, gc2)

                x, (lc, gc) = jax.lax.scan(
                    pair, x, (params["local_blocks"], params["global_blocks"],
                              cache["local"], cache["global"]))
                new_cache = {"local": lc, "global": gc}
            else:
                def body(x, xs):
                    block, c = xs
                    x, c2 = attn_decode(block, x, c, window if self.long_context else 0)
                    if fam == "moe":
                        y, _ = moe_layer(block["moe"], rms_norm(x, block["ln2"]), cfg.top_k,
                                         capacity_factor=cfg.moe_capacity_factor)
                        x = x + y
                    else:
                        x = x + mlp(block["mlp"], rms_norm(x, block["ln2"]))
                    return x, c2

                x, kv2 = jax.lax.scan(body, x, (params["blocks"], cache["kv"]))
                new_cache = {"kv": kv2}
        elif fam == "ssm":
            def body(x, xs):
                block, c = xs
                y, c2 = mamba_lib.mamba1_decode(block["body"], rms_norm(x, block["ln"]),
                                                c, cfg.ssm_state, cfg.dt_rank)
                return x + y, c2

            x, mc = jax.lax.scan(body, x, (params["blocks"], cache["mamba"]))
            new_cache = {"mamba": mc}
        elif fam == "hybrid":
            shared = params["shared_attn"]

            def mstep(x, blk, c):
                y, c2 = mamba_lib.mamba2_decode(blk["body"], rms_norm(x, blk["ln"]),
                                                c, cfg.ssm_state)
                return x + y, c2

            def super_body(x, xs):
                mblocks, mcache, acache = xs

                def inner(x, ys):
                    blk, c = ys
                    return mstep(x, blk, c)

                x, mc2 = jax.lax.scan(inner, x, (mblocks, mcache))
                h, ac2 = attn_lib.decode_attention(
                    shared["attn"], rms_norm(x, shared["ln1"]), positions, acache, **kw)
                x = x + h
                x = x + mlp(shared["mlp"], rms_norm(x, shared["ln2"]))
                return x, (mc2, ac2)

            x, (mc, ac) = jax.lax.scan(
                super_body, x, (params["mamba_blocks"], cache["mamba"], cache["attn"]))
            new_cache = {"mamba": mc, "attn": ac}
            if self.n_tail:
                def tail(x, xs):
                    blk, c = xs
                    return mstep(x, blk, c)

                x, tc = jax.lax.scan(tail, x, (params["tail_blocks"], cache["tail"]))
                new_cache["tail"] = tc
        elif fam == "audio":
            def body(x, xs):
                block, c, ck, cv = xs
                x, c2 = attn_decode(block, x, c, 0)
                h = attn_lib.attention(
                    block["cross"], rms_norm(x, block["ln_cross"]), positions[:, None],
                    causal=False, use_rope=False, kv_override=(ck, cv), kv_positions=None)
                x = x + h
                x = x + mlp(block["mlp"], rms_norm(x, block["ln2"]))
                return x, c2

            x, kv2 = jax.lax.scan(
                body, x, (params["blocks"], cache["kv"], cache["cross_k"], cache["cross_v"]))
            new_cache = {"kv": kv2, "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
        else:
            raise ValueError(fam)

        x = rms_norm(x, params["final_norm"])
        logits = logits_from_embedding(params["embed"], x, cfg.vocab, cfg.final_logit_softcap)
        return logits, new_cache


def build_model(cfg: ArchConfig, shape_name: str = "") -> Model:
    """Factory: the long_500k shape selects the sliding-window variant for
    dense/moe archs (DESIGN.md §Arch-applicability)."""
    long_context = shape_name == "long_500k"
    return Model(cfg, long_context=long_context)
