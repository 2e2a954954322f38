"""The chunked state-space scan is the recurrence it was written from.

:func:`blendjax.ops.ssd.ssd_chunked` (a quadratic form inside chunks, a
carried state between them) against :func:`ssd_sequential` (one token at
a time, float32): values and every input's gradient, at lengths a chunk
divides and does not, in float32 and in the policy's bf16; and the
reason each tolerance is what it is.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blendjax.ops.ssd import ssd_chunked, ssd_sequential
from blendjax.utils.metrics import metrics

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
H, P, G, N = 8, 4, 2, 8


def _inputs(t, dtype, seed=0, batch=2):
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
@pytest.mark.parametrize("t, chunk", [
    (32, 8),    # four whole chunks
    (37, 8),    # a last chunk of 5, padded with dt = 0 tokens
    (13, 16),   # shorter than a chunk: no carried state
])
def test_chunked_scan_is_the_recurrence(t, chunk, dtype):
    x, dt, a, b, c, d = _inputs(t, dtype)
    value_tol, grad_tol = TOLERANCE[dtype]

    def out_and_grads(fn, **kw):
        def loss(*v):
            out = fn(*v, **kw)
            return jnp.sum(
                out.astype(jnp.float32) * jnp.cos(jnp.arange(P))
            ), out

        def both(*v):
            (_, out), grads = jax.value_and_grad(
                loss, tuple(range(6)), has_aux=True
            )(*v)
            return out, grads

        return jax.jit(both)

    want, want_g = out_and_grads(ssd_sequential)(x, dt, a, b, c, d)
    got, got_g = out_and_grads(ssd_chunked, chunk=chunk)(x, dt, a, b, c, d)
    assert got.dtype == dtype and got.shape == x.shape
    assert _rel(got, want) < value_tol
    for name, g, w in zip("x dt a b c d".split(), got_g, want_g):
        assert g.shape == w.shape, name
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


def test_padding_tokens_leave_the_state_alone():
    """A sequence cut after any token gives that prefix's outputs: what
    follows (padding included) never reaches back."""
    x, dt, a, b, c, d = _inputs(37, jnp.float32)
    whole = ssd_chunked(x, dt, a, b, c, d, chunk=8)
    part = ssd_chunked(*(v[:, :21] for v in (x, dt)), a,
                       *(v[:, :21] for v in (b, c)), d, chunk=8)
    assert _rel(part, whole[:, :21]) < TOLERANCE[jnp.float32][0]


def test_the_path_is_counted_once_a_trace():
    x, dt, a, b, c, d = _inputs(16, jnp.float32)
    before = metrics.report()["counters"].get("ssm.path.chunked", 0)
    fn = jax.jit(lambda *v: ssd_chunked(*v, chunk=8))
    fn(x, dt, a, b, c, d)
    fn(x, dt, a, b, c, d)
    after = metrics.report()["counters"]["ssm.path.chunked"]
    assert after == before + 1
