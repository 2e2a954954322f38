#!/usr/bin/env python
"""The Mamba-2 scan alone on the chip, XLA's form against the kernel
pair: what ``ssd.core_device_ms_per_update`` would read if the fused
step made nothing around it.

    python scripts/ssd_scan_time.py [--check] [B,T,H,P,G,N ...]

One JSON line a shape, form (``xla``: ``_ssd_chunked`` under autodiff;
``kernel``: ``ssd_scan_fwd`` / ``ssd_scan_bwd``) and pass (``fwd``;
``fwd_bwd``: the value and all six gradients): ms a call (4 chained
calls a dispatch, bf16, chunk 128, host clock around
``block_until_ready``) and that time's share of what
``benchmark/flops/kernels/ssd_scan.py`` says the recurrence requires
(the reader behind ``ssd_scan_roofline``; the peaks are the benchmark's
table, by ``device_kind``). The default shape is
``nemotron3nano_replay``'s layer: (8, 1200, 64, 64, 8, 128). The fused
step runs the forward twice a layer under ``remat`` and XLA decides what
stands around a custom call from its neighbours' layouts, so the cell's
trace (``--trace 1``) is the judge; this says what the kernels cost
alone. ``--check`` first prints, a shape, the largest difference of the
kernel's value and gradients from the XLA form's, relative to the
latter's largest entry (two roundings of bf16 apart: under 2e-2).
Exits 2 off a TPU: a CPU time says nothing about either form.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax
import jax.numpy as jnp
import numpy as np

from blendjax.ops.ssd import ssd_chunked

SHAPES = [(8, 1200, 64, 64, 8, 128)]
LAYERS, CALLS, CHUNK = 4, 10, 128


def inputs(shape, dtype=jnp.bfloat16):
    bsz, t, h, p, g, n = shape
    k = jax.random.split(jax.random.key(0), 7)
    return (
        jax.random.normal(k[0], (bsz, t, h, p)).astype(dtype),
        jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) - 2.0),
        -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.77)),
        (jax.random.normal(k[3], (bsz, t, g, n)) * n ** -0.5).astype(dtype),
        (jax.random.normal(k[4], (bsz, t, g, n)) * n ** -0.5).astype(dtype),
        1.0 + 0.1 * jax.random.normal(k[5], (h,)),
    ), jax.random.normal(k[6], (bsz, t, h, p), jnp.float32)


def chained(form, layers):
    """``layers`` scans, each reading the one before; ``w`` weighs the
    last (an argument: a closed-over array would be a constant of the
    executable, 157 MB of it)."""
    def loss(x, dt, a, b, c, d, w):
        for _ in range(layers):
            x = ssd_chunked(x, dt, a, b, c, d, chunk=CHUNK, backend=form)
        return jnp.sum(x.astype(jnp.float32) * w)

    return loss


def ms_per_call(step, args):
    for _ in range(2):  # compile, then one warm dispatch
        jax.block_until_ready(step(*args))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = step(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS / LAYERS * 1e3


def required_ms(shape, which, device_kind):
    """The least time the chip's peaks allow one layer's scan."""
    import cells

    bsz, t, h, p, g, n = shape
    work = cells.load_module("flops/kernels", "ssd_scan").required(
        {"patch": 1, "mamba_num_heads": h, "mamba_head_dim": p,
         "n_groups": g, "ssm_state_size": n, "pattern": "M"},
        (t, 1, 4), bsz, "bf16", which,
    )
    return 1e3 * cells.load_module(
        "readers", "trace_kernel_roofline_share"
    ).least_seconds(work, device_kind)


def check(shape):
    args, w = inputs(shape)

    def value_and_y(form):
        def loss(*v):
            y = ssd_chunked(*v[:6], chunk=CHUNK, backend=form)
            return jnp.sum(y.astype(jnp.float32) * v[6]), y

        return jax.jit(jax.value_and_grad(loss, tuple(range(6)), has_aux=True))

    outs = {form: value_and_y(form)(*args, w) for form in ("xla", "kernel")}
    ((_, y_x), g_x), ((_, y_k), g_k) = outs["xla"], outs["kernel"]

    def rel(got, want):
        got, want = (np.asarray(v, np.float64) for v in (got, want))
        return float(np.abs(got - want).max() / np.abs(want).max())

    return {"y": rel(y_k, y_x), **{
        f"d{name}": rel(k, x)
        for name, k, x in zip(("x", "dt", "a", "b", "c", "d"), g_k, g_x)
    }}


def main(argv):
    if jax.default_backend() != "tpu":
        print("ssd_scan_time: no TPU here", file=sys.stderr)
        return 2
    checking = bool(argv) and argv[0] == "--check"
    shapes = [tuple(int(n) for n in a.split(","))
              for a in argv[checking:]] or SHAPES
    kind = jax.devices()[0].device_kind
    for shape in shapes:
        if checking:
            print(json.dumps({"shape": shape, "kernel_minus_xla": check(shape),
                              "device": kind}), flush=True)
        args, w = inputs(shape)
        for form in ("xla", "kernel"):
            loss = chained(form, LAYERS)
            for which, step in (
                ("fwd", jax.jit(loss)),
                ("fwd_bwd", jax.jit(jax.value_and_grad(loss, tuple(range(6))))),
            ):
                ms = ms_per_call(step, (*args, w))
                least = required_ms(
                    shape, "forward" if which == "fwd" else "train", kind
                )
                print(json.dumps({
                    "shape": shape, "form": form, "pass": which,
                    "ms_per_call": ms, "required_ms": least,
                    "roofline_share_pct": 100.0 * least / ms, "device": kind,
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
