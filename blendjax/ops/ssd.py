"""The selective state-space scan of a Mamba-2 mixer (Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060), in the chunked form the MXU
can run.

A head ``h`` of width ``P`` keeps a state ``S`` of ``P x N`` and reads
the ``B`` and ``C`` rows of its group ``h // (H // G)``:

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (x) B_t      S_0 = 0
    y_t = S_t C_t + D_h * x_t

Token by token that is ``T`` dependent steps of elementwise work
(:func:`ssd_sequential`, the form the tests hold the chunked one to).
:func:`ssd_chunked` cuts the sequence into chunks of ``chunk`` tokens:
inside a chunk the recurrence is written out as its quadratic form, a
``chunk x chunk`` decay matrix a head (``exp`` of differences of the
running sum of ``dt * A``, under a causal mask) times ``C_t . B_s``,
applied to ``dt_s x_s`` as batched matrix products; between chunks only
the ``P x N`` state is carried, by a ``lax.scan`` over the chunks. A
length the chunk does not divide is padded with ``dt = 0`` tokens, which
neither decay nor feed the state, and the padding is sliced off.

Precision: ``dt``, ``A``, the running sums, every ``exp`` and the carried
state are float32 (a decay in bf16 is off by 0.4 % a rounding and the
error compounds over a chunk); the matrix products take their operands
in ``x``'s dtype with float32 accumulation. Plain ``jax.numpy`` under
autodiff: no kernel yet. The whole scan runs under the scope ``ssd``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from blendjax.utils.metrics import SCOPE_SSD, metrics


def ssd_sequential(x, dt, a, b, c, d):
    """The recurrence as written, one token at a time in float32:
    ``x`` (B, T, H, P), ``dt`` (B, T, H) after its softplus, ``a`` (H,)
    negative, ``b`` and ``c`` (B, T, G, N), ``d`` (H,) -> (B, T, H, P)
    float32. For tests and tiny sizes."""
    bsz, _, h, p = x.shape
    g, n = b.shape[2:]
    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))  # (B, T, H, N)

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] * b_t[
            ..., None, :
        ]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, y = lax.scan(
        step, jnp.zeros((bsz, h, p, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)),
    )
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def ssd_chunked(x, dt, a, b, c, d, chunk: int = 128):
    """:func:`ssd_sequential`'s result by chunks of ``chunk`` tokens, in
    ``x``'s dtype. Counted once a trace under ``ssm.path.chunked``."""
    metrics.count("ssm.path.chunked")
    with jax.named_scope(SCOPE_SSD):
        return _ssd_chunked(x, dt, a, b, c, d, chunk)


def _ssd_chunked(x, dt, a, b, c, d, chunk):
    dtype = x.dtype
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g  # heads a group
    pad = -t % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    nc = (t + pad) // chunk
    dt = dt.astype(jnp.float32)
    # a head is (group, head of the group); the decays keep the chunk's
    # tokens minor: (B, chunks, G, R, L)
    xc = x.reshape(bsz, nc, chunk, g, r, p)
    dtc = dt.reshape(bsz, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(bsz, nc, chunk, g, n).astype(dtype)
    cc = c.reshape(bsz, nc, chunk, g, n).astype(dtype)
    # running sum of dt * A inside each chunk: log of the decay from the
    # chunk's start up to and including token l
    cum = jnp.cumsum(dtc * a.reshape(g, r, 1), axis=-1)      # f32, <= 0
    total = cum[..., -1]                                     # (B, nc, G, R)

    def per_token(v):  # (B, nc, G, R, L) -> (B, nc, L, G, R, 1)
        return v.transpose(0, 1, 4, 2, 3)[..., None]

    xdt = (xc.astype(jnp.float32) * per_token(dtc)).astype(dtype)

    # -- inside a chunk: the quadratic form ----------------------------------
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                    preferred_element_type=jnp.float32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(cum_l - cum_s) for s <= l; the mask goes in before the exp
    diff = cum[..., :, None] - cum[..., None, :]             # (B,nc,G,R,L,S)
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    mix = (cb[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", mix, xdt,
                   preferred_element_type=jnp.float32)

    # -- what each chunk adds to the state, and the state it starts from ------
    to_end = jnp.exp(total[..., None] - cum)                 # (B, nc, G, R, L)
    added = jnp.einsum(
        "bclgrp,bclgn->bcgrpn",
        (xdt.astype(jnp.float32) * per_token(to_end)).astype(dtype), bc,
        preferred_element_type=jnp.float32,
    )

    def carry(state, inputs):
        chunk_decay, chunk_added = inputs
        return chunk_decay[..., None, None] * state + chunk_added, state

    _, before = lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), jnp.float32),
        (jnp.moveaxis(jnp.exp(total), 1, 0), jnp.moveaxis(added, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)                      # (B,nc,G,R,P,N)
    y = y + per_token(jnp.exp(cum)) * jnp.einsum(
        "bclgn,bcgrpn->bclgrp", cc, before.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    y = y.reshape(bsz, nc * chunk, h, p)[:, :t]
    x = x[:, :t].astype(jnp.float32)
    return (y + d.astype(jnp.float32)[:, None] * x).astype(dtype)
