"""The chunked state-space scan is the recurrence it was written from.

:func:`blendjax.ops.ssd.ssd_chunked` (a quadratic form inside chunks, a
carried state between them) against :func:`ssd_sequential` (one token at
a time, float32): values and every input's gradient, at lengths a chunk
divides and does not, in float32 and in the policy's bf16, by XLA's
fusions and by the kernel pair (in interpreter mode here: what the
chip's compiler says of it is ``tests/test_tpu_compile.py``'s); the
reason each tolerance is what it is; and the rule that picks between the
two.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blendjax.ops.attention import batch_sharded_over
from blendjax.ops.ssd import (
    auto_picks_kernel,
    ssd_chunked,
    ssd_kernel_supported,
    ssd_sequential,
)
from blendjax.utils.metrics import SAVED_RESIDUALS, metrics

# float32: the two forms sum the same products in another order (a
# chunk's 8-32 terms against a running state), a few ulps of values of
# O(10): 2e-6 of the largest value, 1e-5 for gradients, which sum over
# the sequence as well. bf16: x, B, C, dt*x and the chunk's mixing
# matrix are rounded to 8 bits (2^-9 relative each) before products
# accumulated in float32, and the state is rounded once a chunk where it
# is read: five roundings of terms that partly cancel, 3e-2 of the
# largest value (measured 0.6e-2 to 1.6e-2). The decays stay float32 in
# both: rounding dt alone to bf16 fails the float32 bar a thousandfold
# (test_a_bf16_decay_fails_the_float32_bar).
TOLERANCE = {jnp.float32: (2e-6, 1e-5), jnp.bfloat16: (3e-2, 6e-2)}
# At the widths the kernel's blocking takes (a state of 128, chunks of
# 128 tokens) every contraction sums 16 times the terms, and the
# gradient of ``a`` sums each head's decays over every token: float32
# measured up to 4.0e-6 and 8.6e-5 (the XLA form; the kernel 3.3e-6 and
# 4.0e-5), bf16 as above (0.5e-2, 1.4e-2).
WIDE_TOLERANCE = {jnp.float32: (1e-5, 2e-4), jnp.bfloat16: (3e-2, 6e-2)}
H, P, G, N = TINY = 8, 4, 2, 8
# kernel-eligible: a group's R·P and the state fill 128 lanes
ONE_GROUP = 2, 64, 1, 128        # two heads of 64 side by side in a block
TWO_GROUPS = 16, 16, 2, 128      # eight heads of 16 a block, a group axis


def _inputs(t, dtype, seed=0, batch=2, dims=TINY):
    H, P, G, N = dims
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (batch, t, H, P)).astype(dtype)
    # after its softplus, around the published range of time steps
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, t, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77))
    b = jax.random.normal(k[3], (batch, t, G, N)).astype(dtype)
    c = jax.random.normal(k[4], (batch, t, G, N)).astype(dtype)
    d = 1.0 + 0.1 * jax.random.normal(k[5], (H,))
    return x, dt, a, b, c, d


def _rel(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t, chunk, dims, backend", [
    (32, 8, TINY, "xla"),    # four whole chunks
    (37, 8, TINY, "xla"),    # a last chunk of 5, padded with dt = 0 tokens
    (13, 16, TINY, "xla"),   # shorter than a chunk: no carried state
    # where the kernel's blocking takes the shape, both forms:
    *[(t, 128, ONE_GROUP, backend) for backend in ("xla", "kernel")
      for t in (256,     # two whole chunks
                300,     # a ragged last chunk of 44, masked in the kernel
                100)],   # shorter than a chunk
    (300, 128, TWO_GROUPS, "kernel"),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_chunked_scan_is_the_recurrence(t, chunk, dims, backend, dtype):
    x, dt, a, b, c, d = _inputs(t, dtype, dims=dims)
    value_tol, grad_tol = (TOLERANCE if dims == TINY else WIDE_TOLERANCE)[dtype]
    head_dim = dims[1]

    def out_and_grads(fn, **kw):
        def loss(*v):
            out = fn(*v, **kw)
            return jnp.sum(
                out.astype(jnp.float32) * jnp.cos(jnp.arange(head_dim))
            ), out

        def both(*v):
            (_, out), grads = jax.value_and_grad(
                loss, tuple(range(6)), has_aux=True
            )(*v)
            return out, grads

        return jax.jit(both)

    want, want_g = out_and_grads(ssd_sequential)(x, dt, a, b, c, d)
    got, got_g = out_and_grads(ssd_chunked, chunk=chunk, backend=backend)(
        x, dt, a, b, c, d
    )
    assert got.dtype == dtype and got.shape == x.shape
    assert _rel(got, want) < value_tol
    for name, g, w in zip("x dt a b c d".split(), got_g, want_g):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < grad_tol, name


def test_a_bf16_decay_fails_the_float32_bar():
    """The decays are float32 by construction; one bf16 rounding of the
    time step (a softplus computed in bf16) is what the float32 tolerance
    exists to catch."""
    x, dt, a, b, c, d = _inputs(64, jnp.float32)
    want = ssd_sequential(x, dt, a, b, c, d)
    rounded = dt.astype(jnp.bfloat16).astype(jnp.float32)
    got = ssd_chunked(x, rounded, a, b, c, d, chunk=16)
    assert _rel(got, want) > 100 * TOLERANCE[jnp.float32][0]


@pytest.mark.parametrize("dims, chunk, t, cut, backend", [
    (TINY, 8, 37, 21, "xla"),
    (ONE_GROUP, 128, 300, 200, "kernel"),
], ids=["xla", "kernel"])
def test_padding_tokens_leave_the_state_alone(dims, chunk, t, cut, backend):
    """A sequence cut after any token gives that prefix's outputs: what
    follows (padding included, and whatever a ragged block's copy left
    past the end) never reaches back."""
    x, dt, a, b, c, d = _inputs(t, jnp.float32, dims=dims)
    tol = (TOLERANCE if dims == TINY else WIDE_TOLERANCE)[jnp.float32][0]
    whole = ssd_chunked(x, dt, a, b, c, d, chunk=chunk, backend=backend)
    part = ssd_chunked(*(v[:, :cut] for v in (x, dt)), a,
                       *(v[:, :cut] for v in (b, c)), d, chunk=chunk,
                       backend=backend)
    assert np.isfinite(np.asarray(whole)).all()
    assert _rel(part, whole[:, :cut]) < tol


@pytest.mark.parametrize("dims, chunk, t, backend, counted, other", [
    (TINY, 8, 16, "auto", "ssm.path.chunked", "ssm.path.kernel"),
    (ONE_GROUP, 128, 128, "kernel", "ssm.path.kernel", "ssm.path.chunked"),
], ids=["xla", "kernel"])
def test_the_path_is_counted_once_a_trace(dims, chunk, t, backend, counted,
                                          other):
    """One of the two counters a traced call, never both; ``auto`` off a
    TPU is the XLA form."""
    x, dt, a, b, c, d = _inputs(t, jnp.float32, dims=dims)
    before = metrics.report()["counters"]
    fn = jax.jit(lambda *v: ssd_chunked(*v, chunk=chunk, backend=backend))
    fn(x, dt, a, b, c, d)
    fn(x, dt, a, b, c, d)
    after = metrics.report()["counters"]
    assert after[counted] == before.get(counted, 0) + 1
    assert after.get(other, 0) == before.get(other, 0)


def _shapes(batch, t, h, p, g, n):
    return (jax.ShapeDtypeStruct((batch, t, h, p), jnp.bfloat16),
            jax.ShapeDtypeStruct((batch, t, g, n), jnp.bfloat16))


CELL = (8, 1200, 64, 64, 8, 128)       # nemotron3nano_replay's layer
REHEARSAL = (2, 64, 4, 8, 2, 8)        # its `rehearse` block, test_hybrid.py


@pytest.mark.parametrize(
    "shape, chunk, backend, devices, mesh_over, picks, supported", [
        (CELL, 128, "tpu", 1, None, True, True),
        (CELL, 128, "cpu", 1, None, False, True),
        (REHEARSAL, 16, "tpu", 1, None, False, False),
        (CELL, 64, "tpu", 1, None, False, False),      # a chunk under 128 lanes
        ((8, 1200, 64, 64, 16, 128), 128, "tpu", 1, None, False, False),  # R 4
        ((8, 1200, 8, 48, 1, 128), 128, "tpu", 1, None, False, False),  # P 48
        ((2, 300, 2, 64, 1, 128), 128, "tpu", 1, None,
         True, True),
        # several devices and nothing declared: the program may be
        # partitioned, where the lowering refuses a bare kernel
        (CELL, 128, "tpu", 8, None, False, True),
        # a declared mesh: per batch shard where its axis divides the batch
        (CELL, 128, "tpu", 8, 8, True, True),
        ((4, 1200, 64, 64, 8, 128), 128, "tpu", 8, 8, False, True),
        (CELL, 128, "tpu", 8, 1, True, True),          # a one-device mesh
    ],
    ids=["cell", "cell-on-cpu", "rehearsal", "chunk64", "four-heads-a-group",
         "head-of-48", "one-group", "undeclared-8-devices", "mesh8",
         "mesh8-batch4", "mesh1"],
)
def test_which_inputs_take_the_kernel(monkeypatch, shape, chunk, backend,
                                      devices, mesh_over, picks, supported):
    """The eligibility rule as a truth table: ``auto`` takes the kernel
    on a TPU, where the blocking takes the shape and the placement may
    hold a kernel; the XLA form everywhere else."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    x, b = _shapes(*shape)
    assert ssd_kernel_supported(x, b, chunk) == supported
    mesh = None if mesh_over is None else jax.sharding.Mesh(
        np.array(jax.devices()[:mesh_over]), ("data",)
    )
    with batch_sharded_over(mesh):
        assert auto_picks_kernel(x, b, chunk) == picks


def test_the_kernel_runs_per_batch_shard_of_a_declared_mesh():
    """Under a declared mesh the kernel pair runs through ``shard_map``
    over the batch axis (GSPMD cannot partition a custom call): the same
    value and gradients as the undeclared call."""
    v = _inputs(128, jnp.float32, dims=ONE_GROUP)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))

    def loss(*v):
        out = ssd_chunked(*v, chunk=128, backend="kernel")
        return jnp.sum(out * jnp.cos(jnp.arange(out.shape[-1]))), out

    grad = jax.value_and_grad(loss, tuple(range(6)), has_aux=True)
    (_, want), want_g = jax.jit(grad)(*v)
    with batch_sharded_over(mesh):
        (_, got), got_g = jax.jit(grad)(*v)
    assert _rel(got, want) < 1e-6
    for g, w in zip(got_g, want_g):
        assert _rel(g, w) < 1e-5


@pytest.mark.parametrize("policy, forwards", [
    (None, 2),  # plain remat: the backward runs the forward kernel again
    (jax.checkpoint_policies.save_only_these_names(*SAVED_RESIDUALS), 1),
], ids=["plain", "saved"])
def test_remat_keeps_the_kernels_output_and_states(policy, forwards):
    """Under ``remat`` with StreamHybrid's policy the kernel's output and
    the states it starts each chunk from are kept (the names
    ``ssd_y``, ``ssd_states``), so the forward kernel runs once: the
    lowered program calls it once, and the value and gradients are the
    ones without ``remat``."""
    v = _inputs(300, jnp.float32, dims=ONE_GROUP)

    def loss(*v):
        return jnp.sum(jnp.sin(ssd_chunked(*v, chunk=128, backend="kernel")))

    want, want_g = jax.jit(jax.value_and_grad(loss, tuple(range(6))))(*v)
    grad = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss, policy=policy), tuple(range(6))
    ))
    text = grad.lower(*v).as_text()
    assert len(re.findall(r"call @_scan_fwd(_\d+)?\(", text)) == forwards
    assert len(re.findall(r"call @_scan_bwd(_\d+)?\(", text)) == 1
    got, got_g = grad(*v)
    assert _rel(got, want) < 1e-6
    for g, w in zip(got_g, want_g):
        assert _rel(g, w) < 1e-6


def test_an_explicit_kernel_refuses_a_shape_it_cannot_block():
    x, dt, a, b, c, d = _inputs(16, jnp.float32)
    with pytest.raises(ValueError, match="multiples of 128"):
        ssd_chunked(x, dt, a, b, c, d, chunk=8, backend="kernel")
    with pytest.raises(ValueError, match="unknown scan backend"):
        ssd_chunked(x, dt, a, b, c, d, chunk=8, backend="pallas")
