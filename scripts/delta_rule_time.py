#!/usr/bin/env python
"""The gated delta rule alone on the chip, XLA's chunked form against the
kernel pair: what ``delta_rule.core_device_ms_per_update`` would read if
the fused step made nothing around it.

    python scripts/delta_rule_time.py [--check] [B,T,H,K,V ...]

One JSON line a shape, form (``xla``: ``_chunked`` under autodiff;
``kernel``: ``delta_rule_fwd`` / ``delta_rule_bwd``) and pass (``fwd``;
``fwd_bwd``: the value and all five gradients): ms a layer (3 chained
layers a call, as the cell's 3 ``L`` layers; bf16 q, k, v, chunk 64, host
clock around ``block_until_ready``) and that time's share of what
``benchmark/flops/kernels/gated_delta_rule.py`` says the recurrence
requires (the reader behind ``delta_rule_roofline``; the peaks are the
benchmark's table, by ``device_kind``). The default shape is
``olmohybrid_replay``'s layer: (2, 4800, 15, 96, 192). The inputs are the
mixer's kind: unit-norm keys, four in five of them one repeated key (the
stream's background patch), ``beta`` in (0, 2), decays from weak to
strong. The cell's trace (``--trace 1``) is the judge of the fused step;
this says what the kernels cost alone. ``--check`` first prints, a shape,
the largest difference of the kernel's output and gradients from the XLA
form's, relative to the latter's largest entry; of each form's output
from the float32 recurrence token by token; and of each form's five
gradients from float32 autodiff (the XLA form on the same bf16-rounded
inputs held as float32, every product at HIGHEST). Exits 2 off a TPU: a
CPU time says nothing about either form.
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

from blendjax.ops.delta_rule import (
    gated_delta_rule_chunked,
    gated_delta_rule_sequential,
)

SHAPES = [(2, 4800, 15, 96, 192)]
LAYERS, CALLS, CHUNK = 3, 10, 64


def inputs(shape, dtype=jnp.bfloat16):
    bsz, t, h, dk, dv = shape
    k = jax.random.split(jax.random.key(0), 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    keys = unit(jax.random.normal(k[1], (bsz, t, h, dk)))
    background = jax.random.uniform(k[5], (bsz, t, 1, 1)) < 0.8
    keys = jnp.where(background, keys[:1, :1], keys)
    g = -jax.nn.softplus(jax.random.normal(k[3], (bsz, t, h))
                         + jnp.linspace(-6.0, 2.0, h))
    return (
        (unit(jax.random.normal(k[0], (bsz, t, h, dk))) * dk ** -0.5
         ).astype(dtype),
        keys.astype(dtype),
        jax.nn.silu(jax.random.normal(k[2], (bsz, t, h, dv))).astype(dtype),
        g,
        2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (bsz, t, h))),
    ), jax.random.normal(k[6], (bsz, t, h, dv), jnp.float32)


def chained(form, layers):
    """``layers`` recurrences, each reading the one before as its values;
    ``w`` weighs the last (an argument: a closed-over array would be a
    constant of the executable)."""
    def loss(q, k, v, g, beta, w):
        for _ in range(layers):
            o, _ = gated_delta_rule_chunked(q, k, v, g, beta, chunk=CHUNK,
                                            backend=form)
            v = o.astype(q.dtype)
        return jnp.sum(o * w)

    return loss


def ms_per_layer(step, args):
    for _ in range(2):  # compile, then one warm dispatch
        jax.block_until_ready(step(*args))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = step(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS / LAYERS * 1e3


def required_ms(shape, which, device_kind):
    """The least time the chip's peaks allow one layer's recurrence."""
    import cells

    bsz, t, h, dk, dv = shape
    work = cells.load_module("flops/kernels", "gated_delta_rule").required(
        {"patch": 1, "num_heads": h, "key_dim": dk, "value_dim": dv,
         "layer_types": "L"},
        (t, 1, 4), bsz, "bf16", which,
    )
    return 1e3 * cells.load_module(
        "readers", "trace_kernel_roofline_share"
    ).least_seconds(work, device_kind)


def rel(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def check(shape):
    args, w = inputs(shape)

    def value_and_o(form):
        def loss(*v):
            o, _ = gated_delta_rule_chunked(*v[:5], chunk=CHUNK,
                                            backend=form)
            return jnp.sum(o * v[5]), o

        return jax.jit(jax.value_and_grad(loss, tuple(range(5)),
                                          has_aux=True))

    names = ("dq", "dk", "dv", "dg", "dbeta")
    outs = {form: value_and_o(form)(*args, w) for form in ("xla", "kernel")}
    ((_, o_x), g_x), ((_, o_k), g_k) = outs["xla"], outs["kernel"]
    wide = tuple(x.astype(jnp.float32) for x in args)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(gated_delta_rule_sequential)(*args)
        (_, _), truth = value_and_o("xla")(*wide, w)
    return {
        "kernel_minus_xla": {"o": rel(o_k, o_x), **{
            name: rel(k, x) for name, k, x in zip(names, g_k, g_x)
        }},
        "o_minus_float32_recurrence": {"xla": rel(o_x, want),
                                       "kernel": rel(o_k, want)},
        "grads_minus_float32_autodiff": {
            form: {name: rel(g, t) for name, g, t in zip(names, got, truth)}
            for form, got in (("xla", g_x), ("kernel", g_k))
        },
    }


def main(argv):
    if jax.default_backend() != "tpu":
        print("delta_rule_time: no TPU here", file=sys.stderr)
        return 2
    checking = bool(argv) and argv[0] == "--check"
    shapes = [tuple(int(n) for n in a.split(","))
              for a in argv[checking:]] or SHAPES
    kind = jax.devices()[0].device_kind
    for shape in shapes:
        if checking:
            print(json.dumps({"shape": shape, **check(shape),
                              "device": kind}), flush=True)
        args, w = inputs(shape)
        for form in ("xla", "kernel"):
            loss = chained(form, LAYERS)
            grad = jax.value_and_grad(loss, tuple(range(5)))
            for which, step in (("fwd", jax.jit(loss)),
                                ("fwd_bwd", jax.jit(grad))):
                ms = ms_per_layer(step, (*args, w))
                least = required_ms(
                    shape, "forward" if which == "fwd" else "train", kind
                )
                print(json.dumps({
                    "shape": shape, "form": form, "pass": which,
                    "ms_per_layer": ms, "required_ms": least,
                    "roofline_share_pct": 100.0 * least / ms, "device": kind,
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
