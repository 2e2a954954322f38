#!/usr/bin/env python
"""The patch embedding alone, forward + the kernel's gradient, inside a
scan over a decoded superbatch, on the chip: the measurement the form in
``blendjax.ops.image.embed_patches`` was chosen from.

    python scripts/patch_embed_time.py [--trace DIR] [FORM ...]

The program is the fused step's shape in small: a superbatch arrives as
``u8[128, 480, 2560]`` (what the decode kernel writes: frame, row, the
row's 640 x 4 bytes), is viewed as 16 updates of 8 frames, and a
``lax.scan`` takes one update a turn through embedding -> position table
-> LayerNorm -> a weighted sum, differentiates by kernel, bias and table
and applies the gradient, so no turn can be hoisted. One JSON line a
form: ms an update on the host clock around ``block_until_ready``, and
the same less the ``none`` form's (the scan, the LayerNorm and the update
without an embedding). ``--trace DIR`` also writes a profiler trace a
form to ``DIR/<form>/``, which ``python3 benchmark/trace_scopes.py
<file.xplane.pb> --chunk 16 --top 12`` splits by operation. Exits 2 off
a TPU: a CPU time says nothing about any of them.

The forms, with what each took on a v5e less ``none`` (ms an update; my
chip runs, PR 34). ``patches`` is what the model runs,
``blendjax.ops.image.embed_patches``; the others are kept here as what it
was measured against:

``conv``       2.539  the ``p`` x ``p`` stride-``p`` convolution the model had
``patches``    0.688  reshape -> transpose to ``(B, gh, gw, p*p*C)`` written
                      on the u8 frames, scale, one product
``patches_u8`` 1.021  the same with the transposition held on u8 by an
                      ``optimization_barrier`` (two slow u8 passes)
``einsum6``    0.705  ``bhrwxc,rxcd->bhwd`` on the 6-D view
``einsum5``    1.338  ``bhrwk,rkd->bhwd``, a patch row's ``p*C`` bytes one axis
``rows``      13.779  ``p`` products of depth ``p*C``, one a patch row, summed
``lanes``      2.429  ``einsum5`` with two patches side by side in a
                      128-lane register and the kernel block-diagonal
``lanes_conv`` 1.601  the same as a ``(p, 1)``-window convolution of 128 features
``rows_conv``  0.281  ``rows`` as one ``(p, 1)``-window convolution of ``p*C``
                      features: the fastest, and a convolution
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from blendjax.ops.image import embed_patches, maybe_normalize_uint8

UPDATES, B, H, W, C = 16, 8, 480, 640, 4
PATCH, DIM = 16, 768
CALLS = 5
DTYPE = jnp.bfloat16


def _conv(images, kernel, bias):
    x = maybe_normalize_uint8(images, DTYPE)
    return jax.lax.conv_general_dilated(
        x, kernel.astype(DTYPE), (PATCH, PATCH), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ) + bias.astype(DTYPE)


def _views(images):
    b, h, w, c = images.shape
    return b, h // PATCH, w // PATCH, c


def _patches(images, kernel, bias):
    return embed_patches(images, kernel, bias, DTYPE)


def _patches_u8(images, kernel, bias):
    b, gh, gw, c = _views(images)
    x = images.reshape(b, gh, PATCH, gw, -1).transpose(0, 1, 3, 2, 4)
    x = jax.lax.optimization_barrier(x).reshape(b, gh, gw, -1)
    x = maybe_normalize_uint8(x, DTYPE)
    return x @ kernel.astype(DTYPE).reshape(-1, DIM) + bias.astype(DTYPE)


def _einsum6(images, kernel, bias):
    b, gh, gw, c = _views(images)
    x = maybe_normalize_uint8(images, DTYPE).reshape(b, gh, PATCH, gw, PATCH, c)
    return jnp.einsum(
        "bhrwxc,rxcd->bhwd", x, kernel.astype(DTYPE)
    ) + bias.astype(DTYPE)


def _einsum5(images, kernel, bias):
    b, gh, gw, c = _views(images)
    x = maybe_normalize_uint8(images, DTYPE).reshape(b, gh, PATCH, gw, -1)
    k = kernel.astype(DTYPE).reshape(PATCH, -1, DIM)
    return jnp.einsum("bhrwk,rkd->bhwd", x, k) + bias.astype(DTYPE)


def _rows(images, kernel, bias):
    b, gh, gw, c = _views(images)
    x = maybe_normalize_uint8(images, DTYPE).reshape(b, gh, PATCH, gw, -1)
    k = kernel.astype(DTYPE).reshape(PATCH, -1, DIM)
    return sum(x[:, :, r] @ k[r] for r in range(PATCH)) + bias.astype(DTYPE)


def _block_diagonal(kernel, side):
    k = kernel.reshape(PATCH, -1, DIM)
    k = jnp.einsum("qs,rkd->rqksd", jnp.eye(side, dtype=k.dtype), k)
    return k.reshape(PATCH, side * k.shape[2], side * DIM).astype(DTYPE)


def _lanes(images, kernel, bias, conv=False):
    b, gh, gw, c = _views(images)
    side = 128 // (PATCH * c)
    k = _block_diagonal(kernel, side)
    x = maybe_normalize_uint8(images, DTYPE)
    if conv:
        y = jax.lax.conv_general_dilated(
            x.reshape(b, gh * PATCH, gw // side, -1), k[:, None], (PATCH, 1),
            "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    else:
        x = x.reshape(b, gh, PATCH, gw // side, -1)
        y = jnp.einsum("bhrmk,rkn->bhmn", x, k)
    return y.reshape(b, gh, gw, DIM) + bias.astype(DTYPE)


def _rows_conv(images, kernel, bias):
    b, gh, gw, c = _views(images)
    x = maybe_normalize_uint8(images, DTYPE).reshape(b, gh * PATCH, gw, -1)
    k = kernel.astype(DTYPE).reshape(PATCH, 1, -1, DIM)
    return jax.lax.conv_general_dilated(
        x, k, (PATCH, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ) + bias.astype(DTYPE)


def _none(images, kernel, bias):
    b, gh, gw, c = _views(images)
    y = kernel.astype(DTYPE).sum((0, 1, 2)) + bias.astype(DTYPE)
    return jnp.broadcast_to(y, (b, gh, gw, DIM))


FORMS = {
    "none": _none,
    "conv": _conv,
    "patches": _patches,
    "patches_u8": _patches_u8,
    "einsum6": _einsum6,
    "einsum5": _einsum5,
    "rows": _rows,
    "lanes": _lanes,
    "lanes_conv": lambda *a: _lanes(*a, conv=True),
    "rows_conv": _rows_conv,
}


def make_step(form, weight):
    def loss(params, images):
        x = form(images, params["kernel"], params["bias"])
        x = x.reshape(x.shape[0], -1, DIM) + params["pos"].astype(DTYPE)
        x = x.astype(jnp.float32)
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return jnp.mean((x - mean) * jax.lax.rsqrt(var + 1e-6) * weight)

    def body(params, images):
        value, grads = jax.value_and_grad(loss)(params, images)
        return jax.tree_util.tree_map(
            lambda p, g: p - 1e-3 * g, params, grads
        ), value

    @jax.jit
    def step(params, frames):
        superbatch = frames.reshape(UPDATES, B, H, W, C)
        return jax.lax.scan(body, params, superbatch)

    return step


def ms_per_update(step, params, frames, trace_dir=None):
    for _ in range(2):  # compile, then one warm dispatch
        jax.block_until_ready(step(params, frames))
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    start = time.perf_counter()
    for _ in range(CALLS):
        out = step(params, frames)
    jax.block_until_ready(out)
    seconds = time.perf_counter() - start
    if trace_dir:
        jax.profiler.stop_trace()
    return seconds / CALLS / UPDATES * 1e3


def main(argv):
    if jax.default_backend() != "tpu":
        print("patch_embed_time: no TPU here", file=sys.stderr)
        return 2
    trace = None
    if argv and argv[0] == "--trace":
        trace, argv = argv[1], argv[2:]
    names = ["none"] + [n for n in (argv or FORMS) if n != "none"]
    keys = jax.random.split(jax.random.key(0), 4)
    frames = jax.random.randint(
        keys[0], (UPDATES * B, H, W * C), 0, 256, jnp.int32
    ).astype(jnp.uint8)
    params = {
        "kernel": 0.03 * jax.random.normal(
            keys[1], (PATCH, PATCH, C, DIM), jnp.float32
        ),
        "bias": jnp.zeros((DIM,), jnp.float32),
        "pos": 0.02 * jax.random.normal(
            keys[2], (1, (H // PATCH) * (W // PATCH), DIM), jnp.float32
        ),
    }
    weight = jax.random.normal(
        keys[3], (B, (H // PATCH) * (W // PATCH), DIM), jnp.float32
    )
    base = None
    for name in names:
        step = make_step(FORMS[name], weight)
        ms = ms_per_update(
            step, params, frames, trace and os.path.join(trace, name)
        )
        base = ms if name == "none" else base
        loss = float(step(params, frames)[1][-1])
        print(json.dumps({
            "form": name, "ms_per_update": ms, "less_none": ms - base,
            "last_loss": loss, "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
