#!/usr/bin/env python
"""Block-size sweep of the fused causal flash-attention kernel on a TPU.

Usage (from the repo root, on a TPU)::

  PYTHONPATH=src python tools/flash_sweep.py                # the train cells' shapes
  PYTHONPATH=src python tools/flash_sweep.py --shape 8,15,5,2048,64

For each shape ``batch,heads,kv_heads,seq,head_dim`` (bf16, causal, scale
``head_dim ** -0.5``) it times, on the host clock around work that ends in
``block_until_ready``, the mean over ``--iters`` back-to-back calls after a
warm-up:

* ``fwd``: the forward kernel alone, for each forward tile ``(block_q, block_k)``;
* ``dkv`` and ``dq``: ``value_and_grad`` of the kernel, varying the tiles of
  one backward kernel while the forward and the other backward kernel keep
  128 (the differences between rows are that kernel's);
* ``rule``: ``value_and_grad`` at the tiles ``models.attention.flash_block_sizes``
  picks, and ``layer``: ``value_and_grad`` of one attention layer of that
  width (projections, RoPE, GQA expansion) by the fused path and by the
  query-block scan.

Prints one JSON line per measurement. It exits off a TPU: the times are the
chip's or nothing.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as tpu_flash

from repro.models import attention as attn_lib

CELL_SHAPES = ((8, 15, 5, 2048, 64), (4, 32, 8, 4096, 64))  # smollm s2048b8, granite s4096b4
FWD = ((128, 128), (256, 256), (512, 512), (256, 512), (512, 256), (1024, 512),
       (512, 1024), (1024, 1024))
DKV = ((128, 128), (256, 256), (512, 512), (256, 512), (512, 256), (1024, 512),
       (512, 1024))
DQ = ((128, 128), (256, 256), (512, 512), (256, 128), (512, 128), (1024, 128),
      (512, 256), (1024, 256))
BASE = {"fwd": (128, 128), "dkv": (128, 128), "dq": (128, 128)}  # the kernel's default tiles


def timed_ms(fn, *args, iters: int) -> float:
    jax.block_until_ready(fn(*args))  # compile and warm up
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def fits(tiles, s: int) -> bool:
    return all(t <= s and s % t == 0 for t in tiles)


def sweep(shape, iters: int):
    b, h, n_kv, s, hd = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (b, h, s, hd), jnp.float32).astype(jnp.bfloat16)
               for key in (kq, kk, kv))

    def attend(caps):
        bs = attn_lib.flash_block_sizes(s, **caps)
        return lambda q_, k_, v_: tpu_flash.flash_attention(
            q_, k_, v_, causal=True, sm_scale=hd ** -0.5, block_sizes=bs)

    def grads(caps):
        f = attend(caps)
        return jax.jit(jax.value_and_grad(
            lambda q_, k_, v_: jnp.sum(f(q_, k_, v_).astype(jnp.float32)), argnums=(0, 1, 2)))

    def emit(kind, tiles, ms):
        print(json.dumps({"shape": list(shape), "kind": kind, "tiles": tiles, "ms": ms}),
              flush=True)

    for t in FWD:
        if fits(t, s):
            emit("fwd", t, timed_ms(jax.jit(attend({**BASE, "fwd": t})), q, k, v, iters=iters))
    for t in DKV:
        if fits(t, s):
            emit("dkv", t, timed_ms(grads({**BASE, "dkv": t}), q, k, v, iters=iters))
    for t in DQ:
        if fits(t, s):
            emit("dq", t, timed_ms(grads({**BASE, "dq": t}), q, k, v, iters=iters))
    emit("rule", None, timed_ms(grads({}), q, k, v, iters=iters))

    kp, kx = jax.random.split(jax.random.PRNGKey(1))
    d = h * hd
    params = attn_lib.init_attention(kp, d, h, n_kv, hd, jnp.bfloat16)
    x = jax.random.normal(kx, (b, s, d), jnp.float32).astype(jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    for fused in (True, False):
        layer = jax.jit(jax.value_and_grad(lambda p, x_: jnp.sum(attn_lib.attention(
            p, x_, pos, positions_are_rows=fused).astype(jnp.float32)), argnums=(0, 1)))
        emit("layer", "fused" if fused else "scan", timed_ms(layer, params, x, iters=iters))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", action="append",
                    help="batch,heads,kv_heads,seq,head_dim (repeatable; default: the "
                         "train cells')")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_sweep times the kernel on a TPU")
    shapes = [tuple(int(n) for n in s.split(",")) for s in args.shape] if args.shape \
        else CELL_SHAPES
    for shape in shapes:
        sweep(shape, args.iters)


if __name__ == "__main__":
    main()
