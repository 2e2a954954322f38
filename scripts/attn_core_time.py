#!/usr/bin/env python
"""The attention core alone, forward + backward, materialised against
fused, on the chip: the measurement ``FLASH_RESIDUAL_BYTES`` in
``blendjax/ops/attention.py`` is set from.

    python scripts/attn_core_time.py [--block-q N,N,...] [B,T,H,D ...]
    python scripts/attn_core_time.py --projected [B,T,H,D ...]

One JSON line a shape and backend: ms a call (12 chained calls a
dispatch, bf16, host clock around ``block_until_ready``), the bytes of
scores the ``auto`` policy reads and, for the kernel, the launch
geometry. ``--block-q`` times the kernel at each of the given query
blocks in place of the one ``flash_block_sizes`` computes — the sweep
FLASH_TILE_ELEMS is set from; the library itself has no such argument,
the script replaces the function for the run. A block that does not
divide T pads Q to it. ``--projected`` times what the core alone cannot
show: the ``qkv`` projection, the core and the projection's gradients
together, the kernels on three tensors sliced from ``DenseGeneral``'s
product against the packed path (the flat product into
``local_attention_packed``). The layout copies XLA puts around a custom
call live between the product and the kernel, so the parameters ride a
scan's carry and take an sgd update there, as they do in the fused step,
and XLA picks their layout as it does there. Exits 2 off a TPU: a CPU
time says nothing about either path.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from blendjax.ops import attention as A
from blendjax.ops.attention import (
    attention_reads_packed,
    local_attention,
    local_attention_packed,
    packed_qkv_projection,
    scores_residual_bytes,
)

SHAPES = [(8, 197, 12, 64), (8, 768, 4, 128), (8, 1200, 12, 64),
          (4, 3072, 4, 128)]
LAYERS, CALLS = 12, 10


def ms_per_call(backend, q, k, v, w):
    def loss(q, k, v):
        for _ in range(LAYERS):
            q = local_attention(q, k, v, backend=backend)
        return jnp.sum(q.astype(jnp.float32) * w)

    step = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))
    for _ in range(2):  # compile, then one warm dispatch
        jax.block_until_ready(step(q, k, v))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = step(q, k, v)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS / LAYERS * 1e3


def ms_per_projected_layer(path, shape, updates=4):
    """Projection + core + both of the projection's gradients, ms a
    layer an update: ``LAYERS`` chained ``x -> core(qkv(x))`` blocks of
    width H·D, ``updates`` sgd updates in one scanned dispatch."""
    b, t, h, d = shape
    c = h * d
    keys = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(keys[0], (b, t, c), jnp.bfloat16)
    w = jax.random.normal(keys[1], (b, t, c), jnp.float32)
    params = {
        "kernel": jax.random.normal(keys[2], (LAYERS, c, 3, h, d)) * c ** -0.5,
        "bias": jnp.zeros((LAYERS, 3, h, d)),
    }

    def layer(x, kernel, bias):
        if path == "packed":
            o = local_attention_packed(
                packed_qkv_projection(x, kernel, x.dtype), h,
                bias=bias.reshape(-1).astype(x.dtype), backend="flash",
            )
        else:
            qkv = jnp.einsum(
                "btc,cshd->btshd", x, kernel.astype(x.dtype)
            ) + bias.astype(x.dtype)
            o = local_attention(*(qkv[:, :, i] for i in range(3)),
                                backend="flash")
        return o.reshape(x.shape)

    def loss(params):
        y = x
        for i in range(LAYERS):
            y = layer(y, params["kernel"][i], params["bias"][i])
        return jnp.sum(y.astype(jnp.float32) * w)

    @jax.jit
    def step(params):
        def update(params, _):
            value, grads = jax.value_and_grad(loss)(params)
            return jax.tree_util.tree_map(
                lambda p, g: p - 1e-6 * g, params, grads
            ), value

        return jax.lax.scan(update, params, None, length=updates)

    for _ in range(2):  # compile, then one warm dispatch
        jax.block_until_ready(step(params))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = step(params)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS / updates / LAYERS * 1e3


def with_block_q(block_q):
    """``flash_block_sizes`` with the query block replaced."""
    rule = A.flash_block_sizes

    def forced(t_q, t_kv, dtype=jnp.bfloat16):
        padded_q = -(-t_q // block_q) * block_q
        return A.FlashBlocks(block_q, padded_q, rule(t_q, t_kv, dtype).padded_kv)

    return forced


def main(argv):
    if jax.default_backend() != "tpu":
        print("attn_core_time: no TPU here", file=sys.stderr)
        return 2
    if argv and argv[0] == "--projected":
        for shape in [tuple(int(n) for n in a.split(","))
                      for a in argv[1:]] or [(8, 1200, 12, 64)]:
            for path in ("three", "packed"):
                print(json.dumps({
                    "shape": shape, "path": path,
                    "reads_packed": attention_reads_packed(
                        *shape, jnp.bfloat16, "flash"),
                    "ms_per_layer": ms_per_projected_layer(path, shape),
                    "device": jax.devices()[0].device_kind,
                }), flush=True)
        return 0
    sweep = []
    if argv and argv[0] == "--block-q":
        sweep, argv = [int(n) for n in argv[1].split(",")], argv[2:]
    shapes = [tuple(int(n) for n in a.split(",")) for a in argv] or SHAPES
    rule = A.flash_block_sizes
    for shape in shapes:
        keys = jax.random.split(jax.random.key(0), 4)
        q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
                   for key in keys[:3])
        w = jax.random.normal(keys[3], shape, jnp.float32)
        runs = [("flash", n) for n in sweep] or [("xla", None), ("flash", None)]
        for backend, block_q in runs:
            A.flash_block_sizes = with_block_q(block_q) if block_q else rule
            print(json.dumps({
                "shape": shape, "backend": backend,
                "scores_bytes": scores_residual_bytes(q),
                "blocks": tuple(
                    A.flash_block_sizes(shape[1], shape[1], q.dtype)
                ) if backend == "flash" else None,
                "ms_per_call": ms_per_call(backend, q, k, v, w),
                "device": jax.devices()[0].device_kind,
            }), flush=True)
        A.flash_block_sizes = rule
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
