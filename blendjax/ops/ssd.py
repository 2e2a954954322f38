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
applied to ``dt_s x_s`` as matrix products; between chunks only the
``P x N`` state is carried. Padding tokens have ``dt = 0``, which
neither decays nor feeds the state.

Two implementations of that one algorithm, chosen by what the input
shows (:func:`auto_picks_kernel`), never by a caller's option:

- the kernel pair ``ssd_scan_fwd`` / ``ssd_scan_bwd`` under one
  ``jax.custom_vjp`` (:func:`_ssd_scan`), on a TPU wherever the shape
  fits its blocking (:func:`ssd_kernel_supported`). A grid step is one
  (batch row, group, chunk): it reads the chunk's ``x`` (L, R·P), ``B``
  and ``C`` (L, N, the group's: never broadcast to heads) and ``dt`` out
  of the (B, T, H·P) and (B, T, G·N) arrays the mixer has them in, at the
  sequence's own length (the ragged last chunk is masked inside), and
  keeps the running sums, a head's ``L x L`` decay matrix, ``C Bᵀ``, the
  mixing matrix and the group's carried state (N, R·P float32, a VMEM
  scratch over the chunk axis, which runs in order) in VMEM. Nothing of
  size ``L x L`` reaches HBM. The forward under differentiation also
  writes the state each chunk starts from (float32: the backward's
  residual); the backward walks the chunks in reverse with the state's
  cotangent in VMEM, recomputes decays and ``C Bᵀ`` from the inputs and
  writes ``dx``, ``ddt``, ``dB`` and ``dC`` (summed over a group's heads
  inside) and per-head sums for ``dA`` and ``dD``. Only ``dt``, 4 bytes a
  token a head, is re-laid out around the kernels (tokens minor);
- XLA's fusions over ``jax.numpy`` under autodiff (:func:`_ssd_chunked`):
  everywhere else, and the form the kernel is written from term by term.
  A length the chunk does not divide is padded and the padding sliced
  off; the state is carried by a ``lax.scan`` over the chunks.

Precision, both: ``dt``, ``A``, the running sums, every ``exp`` and the
carried state are float32 (a decay in bf16 is off by 0.4 % a rounding
and the error compounds over a chunk; the kernel's running sum is an MXU
product against a triangle at ``HIGHEST``); the matrix products take
their operands in ``x``'s dtype with float32 accumulation, rounded where
the XLA form rounds them (``dt·x``, the mixing matrix, the state where it
is read). The whole scan, the kernel's backward too, runs under the
scope ``ssd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from blendjax.ops.attention import (
    _LANES,
    _NT,
    _TN,
    _only,
    _placement,
    shard_over_batch,
)
from blendjax.utils.metrics import (
    KERNEL_SSD_BWD,
    KERNEL_SSD_FWD,
    RESIDUAL_SSD_STATES,
    RESIDUAL_SSD_Y,
    SCOPE_SSD,
    metrics,
    saved_residual,
)

_NN = (((1,), (0,)), ((), ()))  # a @ b
# What a grid step of the backward holds: the blocks in flight and some
# thirty (L, R·P) float32 intermediates; 9 MB at the benchmark's shape
# (L 128, R·P 512, N 128). The limit is what the kernels ask of the
# v5e's 128 MiB of VMEM (tests/test_tpu_compile.py compiles under it).
SSD_VMEM_BYTES = 64 << 20


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


def ssd_kernel_supported(x, b, chunk: int) -> bool:
    """Whether the kernel pair's blocking takes ``x`` (B, T, H, P) with
    ``b`` (B, T, G, N) at this ``chunk``: ``chunk``, ``N`` and a group's
    ``R·P`` lanes multiples of 128, heads that fill 128-lane blocks
    whole (``P`` a divisor or a multiple of 128), a group's ``R`` rows of
    ``dt`` whole sublane tiles (or all of them), and a grid step's
    working set (:func:`_vmem_bytes`) within SSD_VMEM_BYTES."""
    if not (x.ndim == 4 and b.ndim == 4) or x.shape[2] % b.shape[2]:
        return False
    h, p = x.shape[2:]
    g, n = b.shape[2:]
    r = h // g
    return (
        chunk % _LANES == 0 and n % _LANES == 0 and (r * p) % _LANES == 0
        and (_LANES % p == 0 or p % _LANES == 0)
        and (r % 8 == 0 or g == 1)
        and _vmem_bytes(chunk, r * p, n) <= SSD_VMEM_BYTES
    )


def _vmem_bytes(chunk: int, width: int, n: int) -> int:
    """A grid step of the backward, the larger of the two: about thirty
    (L, R·P) and ten each of (N, R·P), (L, L) and (L, N) float32
    values."""
    return 4 * (30 * chunk * width + 10 * (n * width + chunk * chunk
                                           + chunk * n))


def auto_picks_kernel(x, b, chunk: int) -> bool:
    """The ``auto`` policy: on a TPU, the kernel pair wherever its
    blocking takes the shape and the program being traced may hold a
    kernel (``ops/attention.py`` ``_placement``: a single-device
    program, or a declared mesh whose batch axes divide the batch, per
    shard); the XLA form everywhere else, which partitions like any
    other operation."""
    if jax.default_backend() != "tpu" or not ssd_kernel_supported(x, b, chunk):
        return False
    placed = _placement()
    return placed == "bare" or (
        placed is not None and x.shape[0] % placed[2] == 0
    )


def ssd_chunked(x, dt, a, b, c, d, chunk: int = 128, backend: str = "auto"):
    """:func:`ssd_sequential`'s result by chunks of ``chunk`` tokens, in
    ``x``'s dtype.

    ``backend``: ``"auto"`` (:func:`auto_picks_kernel`) | ``"xla"`` |
    ``"kernel"``, which raises on a shape the blocking does not take and
    off a TPU runs the kernels in interpreter mode (the tests' way in).
    Counted once a trace under ``ssm.path.kernel`` or
    ``ssm.path.chunked`` (the XLA form)."""
    if backend not in ("auto", "xla", "kernel"):
        raise ValueError(f"unknown scan backend {backend!r}")
    if backend == "kernel" and not ssd_kernel_supported(x, b, chunk):
        raise ValueError(
            "scan kernel requested but its blocking does not take x "
            f"{x.shape} with B/C {b.shape} at chunk {chunk}: chunk, N and "
            "R·P must be multiples of 128, P a divisor or multiple of 128"
        )
    use_kernel = backend == "kernel" or (
        backend == "auto" and auto_picks_kernel(x, b, chunk)
    )
    with jax.named_scope(SCOPE_SSD):
        if not use_kernel:
            metrics.count("ssm.path.chunked")
            return _ssd_chunked(x, dt, a, b, c, d, chunk)
        metrics.count("ssm.path.kernel")

        def scan(x, dt, b, c, a, d):
            return _ssd_scan(x, dt, a, b, c, d, chunk)

        placed = _placement()
        if isinstance(placed, tuple):
            scan = shard_over_batch(scan, placed, x.shape[0], 4, 2)
        return scan(x, dt, b, c, a, d)


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


# -- the kernel pair -----------------------------------------------------------
#
# A grid step is one (batch row, group, chunk). Per-head, per-token
# scalars come in two orientations: "rows" (R, L), tokens in lanes, as
# ``dt`` arrives and as the decay matrix wants its key side; "cols"
# (L, R), tokens in sublanes, its query side; and "over lanes"
# (L, R·P), a head's scalar repeated over its P lanes, as the
# (L, R·P) operands are scaled. The carried state is held transposed,
# (N, R·P): all heads of the group side by side in lanes, so the
# read-out and the update are one full-width product each.


def _head_blocks(r: int, p: int):
    """The group's heads by lane block of an (L, R·P) operand:
    ``[(lanes, [(head, its lane mask inside the block or None)])]``.
    ``P`` < 128: 128 // P heads share a 128-lane block, and a head's
    operand is the block with the other heads' lanes zeroed (a
    contraction or a result over 128 lanes costs the MXU what 64 do)."""
    if p % _LANES == 0:
        return [(slice(h * p, (h + 1) * p), [(h, None)]) for h in range(r)]
    per = _LANES // p
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return [
        (slice(j * _LANES, (j + 1) * _LANES),
         [(j * per + k, (lane >= k * p) & (lane < (k + 1) * p))
          for k in range(per)])
        for j in range(r // per)
    ]


def _over_lanes(cols, p: int):
    """(L, R) per-head columns -> (L, R·P)."""
    rows, r = cols.shape
    parts = []
    for lanes, heads in _head_blocks(r, p):
        out = None
        for h, mask in heads:
            v = jnp.broadcast_to(cols[:, h:h + 1],
                                 (rows, lanes.stop - lanes.start))
            out = v if out is None else jnp.where(mask, v, out)
        parts.append(out)
    return jnp.concatenate(parts, axis=1)


def _dot(a, b, dims=_NN, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


# A float32 product that stays float32 on the MXU (six bf16 passes): the
# running sums and the per-head sums.
_f32_dot = functools.partial(_dot, precision=lax.Precision.HIGHEST)


class _Chunk:
    """What both kernels compute of a chunk before any product: the
    operands with the rows past ``t`` zeroed, the running sum of
    ``dt·A`` in both orientations and the three per-token scales over
    lanes."""

    def __init__(self, refs, chunk_index, t, p):
        x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref = refs
        size = x_ref.shape[1]
        x, self.b, self.c = x_ref[0], b_ref[0], c_ref[0]
        self.dt_rows = dt_ref[0]                              # (R, L) f32
        self.valid = None
        if t % size:  # the last chunk is ragged: what lies past t is
            # whatever the block's copy left there, made dt = 0 tokens
            at = chunk_index * size
            self.valid = at + lax.broadcasted_iota(
                jnp.int32, (size, 1), 0) < t
            x, self.b, self.c = (self.rows(v) for v in (x, self.b, self.c))
            self.dt_rows = jnp.where(
                at + lax.broadcasted_iota(jnp.int32, (1, size), 1) < t,
                self.dt_rows, 0.0,
            )
        self.dtype = x.dtype
        self.a = a_ref[0]                                     # (R, 1)
        self.d = d_ref[0]                                     # (1, R·P)
        row = lax.broadcasted_iota(jnp.int32, (size, size), 0)
        col = lax.broadcasted_iota(jnp.int32, (size, size), 1)
        self.lower = row >= col
        # cum[r, l] = sum over s <= l of dt[r, s] * a[r]
        self.cum_rows = _f32_dot(self.dt_rows * self.a,
                                 (row <= col).astype(jnp.float32))
        self.cum_cols = self.cum_rows.T                       # (L, R)
        self.dt = _over_lanes(self.dt_rows.T, p)
        self.decay = _over_lanes(jnp.exp(self.cum_cols), p)   # from the start
        self.to_end = _over_lanes(
            jnp.exp(self.cum_cols[size - 1:size] - self.cum_cols), p
        )
        self.whole = self.decay[size - 1:size]                # (1, R·P)
        self.x = x.astype(jnp.float32)
        self.xdt = (self.x * self.dt).astype(self.dtype)
        self.cb = _dot(self.c, self.b, _NT)                   # (L, L) f32

    def rows(self, v):
        return v if self.valid is None else jnp.where(
            self.valid, v, jnp.zeros_like(v))

    def head_decay(self, h):
        """exp(cum_l - cum_s) for s <= l, 0 above: the mask goes in
        before the exp."""
        diff = self.cum_cols[:, h:h + 1] - self.cum_rows[h:h + 1, :]
        return jnp.exp(jnp.where(self.lower, diff, -jnp.inf))


def _scan_fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, *rest,
                     t, p):
    """``rest``: the output of the states the chunks start from, where
    the backward will want them, and the carried state's scratch."""
    from jax.experimental import pallas as pl

    state = rest[-1]
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    ch = _Chunk((x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref), i, t, p)
    if len(rest) == 2:
        rest[0][0, 0, 0] = state[...]
    parts = []
    for lanes, heads in _head_blocks(dt_ref.shape[1], p):
        block = ch.xdt[:, lanes]
        parts.append(sum(
            _dot((ch.cb * ch.head_decay(h)).astype(ch.dtype),
                 _only(block, mask))
            for h, mask in heads
        ))
    y = jnp.concatenate(parts, axis=1)
    y = y + ch.decay * _dot(ch.c, state[...].astype(ch.dtype))
    y_ref[0] = (y + ch.d * ch.x).astype(y_ref.dtype)
    state[...] = state[...] * ch.whole + _dot(
        ch.b, (ch.xdt.astype(jnp.float32) * ch.to_end).astype(ch.dtype), _TN
    )


def _scan_bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, st_ref,
                     dy_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                     dstate, *, t, p):
    """One chunk's part of every gradient, the chunks in reverse: the
    cotangent of the state a chunk leaves is in ``dstate`` when the
    chunk runs, that of the state it starts from when it is done."""
    from jax.experimental import pallas as pl

    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    r = dt_ref.shape[1]
    ch = _Chunk((x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref),
                pl.num_programs(2) - 1 - i, t, p)
    dtype = ch.dtype
    dy = ch.rows(dy_ref[0])
    dy32 = dy.astype(jnp.float32)
    u32 = ch.xdt.astype(jnp.float32)
    before = st_ref[0, 0, 0]                                  # (N, R·P) f32
    read = before.astype(dtype)
    dnext = dstate[...]
    dnext_read = dnext.astype(dtype)
    # the state's update: S' = whole * S + Bᵀ (to_end * xdt)
    du_state = _dot(ch.b, dnext_read) * ch.to_end
    db = _dot((u32 * ch.to_end).astype(dtype), dnext_read, _NT)
    # the entering state's read-out: decay * (C S)
    scaled = (ch.decay * dy32).astype(dtype)
    dc = _dot(scaled, read, _NT)
    dstate[...] = dnext * ch.whole + _dot(ch.c, scaled, _TN)
    # inside the chunk, a head at a time: y = mix xdt, mix = cb * decay
    dcb = jnp.zeros_like(ch.cb)
    inside, du = [], []
    for lanes, heads in _head_blocks(r, p):
        block, dy_block = ch.xdt[:, lanes], dy[:, lanes]
        y_part = du_part = 0.0
        for h, mask in heads:
            xdt_h, dy_h = _only(block, mask), _only(dy_block, mask)
            decay = ch.head_decay(h)
            mix = (ch.cb * decay).astype(dtype)
            y_part = y_part + _dot(mix, xdt_h)
            du_part = du_part + _dot(mix, dy_h, _TN)
            dcb = dcb + _dot(dy_h, xdt_h, _NT) * decay
        inside.append(y_part)
        du.append(du_part)
    dcb = dcb.astype(dtype)
    dc_ref[0] = (dc + _dot(dcb, ch.b)).astype(dc_ref.dtype)
    db_ref[0] = (db + _dot(dcb, ch.c, _TN)).astype(db_ref.dtype)
    du = jnp.concatenate(du, axis=1) + du_state
    dx_ref[0] = (du * ch.dt + ch.d * dy32).astype(dx_ref.dtype)
    # the running sum's cotangent a token a head: + what the token's
    # output took through decays that end at it (dy . (y - D x)), - what
    # later tokens and the leaving state took through decays that start
    # at it (d(xdt) . xdt)
    y_decayed = jnp.concatenate(inside, axis=1) + ch.decay * _dot(ch.c, read)
    heads_of = lax.broadcasted_iota(jnp.int32, (r, r * p), 0)
    lane_of = lax.broadcasted_iota(jnp.int32, (r, r * p), 1)
    seg = ((lane_of >= heads_of * p) & (lane_of < (heads_of + 1) * p)
           ).astype(jnp.float32)                              # (R, R·P)

    def per_head(v):  # (M, R·P) -> (R, M): the sum over a head's lanes
        return _f32_dot(seg, v, _NT)

    def per_head_sum(v):  # (L or N, R·P) -> (R, 1): over rows as well
        v = jnp.sum(v, axis=0, keepdims=True)
        return per_head(jnp.broadcast_to(v, (8, v.shape[1])))[:, :1]

    dcum = per_head(dy32 * y_decayed - du * u32)              # (R, L)
    dwhole = per_head_sum(du_state * u32) + per_head_sum(
        dnext * before * ch.whole)
    dda = _f32_dot(dcum, ch.lower.astype(jnp.float32)) + dwhole
    ddt_ref[0] = per_head(du * ch.x) + dda * ch.a
    da_ref[0, 0] += jnp.broadcast_to(
        jnp.sum(dda * ch.dt_rows, axis=1, keepdims=True), da_ref.shape[2:])
    dd_ref[0, 0] += jnp.broadcast_to(
        per_head_sum(dy32 * ch.x), dd_ref.shape[2:])


def _launch(arrays, chunk, reverse):
    """The kernels' static arguments, then grid, scratch and compiler
    parameters, block specs by kind and the shapes of what only the
    kernels make, for one of the pair over ``arrays``: x (B, T, H·P), dt (B, H, T), a (G, R, 1), d
    (G, 1, R·P), b and c (B, T, G·N). ``reverse``: the grid's chunk
    axis walks the chunks last to first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x, _, a, _, b, _ = arrays
    bsz, t, _ = x.shape
    g, r, _ = a.shape
    width, n = x.shape[2] // g, b.shape[2] // g
    nc = -(-t // chunk)

    def at(k):
        return nc - 1 - k if reverse else k

    specs = {
        "x": pl.BlockSpec((1, chunk, width), lambda i, j, k: (i, at(k), j)),
        "dt": pl.BlockSpec((1, r, chunk), lambda i, j, k: (i, j, at(k))),
        "bc": pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, at(k), j)),
        "a": pl.BlockSpec((1, r, 1), lambda i, j, k: (j, 0, 0)),
        "d": pl.BlockSpec((1, 1, width), lambda i, j, k: (j, 0, 0)),
        "state": pl.BlockSpec((1, 1, 1, n, width),
                              lambda i, j, k: (i, j, at(k), 0, 0)),
        "sums": pl.BlockSpec((1, 1, r, _LANES), lambda i, j, k: (i, j, 0, 0)),
    }
    shapes = {
        "state": jax.ShapeDtypeStruct((bsz, g, nc, n, width), jnp.float32),
        "sums": jax.ShapeDtypeStruct((bsz, g, r, _LANES), jnp.float32),
    }
    kernel_args = dict(t=t, p=width // r)
    return kernel_args, dict(
        grid=(bsz, g, nc),
        scratch_shapes=[pltpu.VMEM((n, width), jnp.float32)],
        # the carried state is a scratch over the chunk axis: the chunks
        # of a (batch row, group) run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SSD_VMEM_BYTES,
        ),
        interpret=jax.default_backend() != "tpu",
    ), specs, shapes


_INPUTS = ("x", "dt", "a", "d", "bc", "bc")


# Both launches are jitted: a model's layers call them with the same
# shapes, and the kernel's body, a few hundred operations for a group's
# heads written out, is then traced once a program and not once a layer
# and pass (12 times in the benchmark's step: 2.7 s of set-up).
@functools.partial(jax.jit, static_argnames=("chunk", "save_states"))
def _scan_fwd(arrays, chunk, save_states):
    """y and, with ``save_states``, the state each chunk starts from."""
    from jax.experimental import pallas as pl

    static, call, specs, shapes = _launch(arrays, chunk, reverse=False)
    x = arrays[0]
    outs = [(specs["x"], jax.ShapeDtypeStruct(x.shape, x.dtype))]
    if save_states:
        outs.append((specs["state"], shapes["state"]))
    return pl.pallas_call(
        functools.partial(_scan_fwd_kernel, **static),
        in_specs=[specs[k] for k in _INPUTS],
        out_specs=[spec for spec, _ in outs],
        out_shape=[shape for _, shape in outs],
        name=KERNEL_SSD_FWD, **call,
    )(*arrays)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _scan_bwd(arrays, states, dy, chunk):
    """dx, ddt (B, H, T), db, dc and the per-(batch row, head) sums of
    dA and dD, (B, G, R, 128) with the sum in every lane."""
    from jax.experimental import pallas as pl

    static, call, specs, shapes = _launch(arrays, chunk, reverse=True)
    x, dt, _, _, b, c = arrays
    outs = [
        (specs["x"], x), (specs["dt"], dt), (specs["bc"], b),
        (specs["bc"], c), (specs["sums"], shapes["sums"]),
        (specs["sums"], shapes["sums"]),
    ]
    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, **static),
        in_specs=[specs[k] for k in _INPUTS + ("state", "x")],
        out_specs=[spec for spec, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for _, v in outs],
        name=KERNEL_SSD_BWD, **call,
    )(*arrays, states, dy)


def _kernel_operands(x, dt, a, b, c, d):
    """The six inputs as the kernels read them: x, b and c as the mixer
    has them with heads (groups) folded into lanes, which is a reshape;
    dt tokens-minor in float32, the one re-layout (4 bytes a token a
    head); a a group's column, d over its heads' lanes."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    return (
        x.reshape(bsz, t, h * p),
        jnp.swapaxes(dt.astype(jnp.float32), 1, 2),
        a.astype(jnp.float32).reshape(g, r, 1),
        jnp.repeat(d.astype(jnp.float32), p).reshape(g, 1, r * p),
        b.reshape(bsz, t, g * n).astype(x.dtype),
        c.reshape(bsz, t, g * n).astype(x.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_scan(x, dt, a, b, c, d, chunk):
    """The chunked scan as the kernel pair: (B, T, H, P) in ``x``'s
    dtype, every input differentiable."""
    arrays = _kernel_operands(x, dt, a, b, c, d)
    (y,) = _scan_fwd(arrays, chunk, save_states=False)
    return y.reshape(x.shape)


def _ssd_scan_fwd(x, dt, a, b, c, d, chunk):
    arrays = _kernel_operands(x, dt, a, b, c, d)
    y, states = _scan_fwd(arrays, chunk, save_states=True)
    # named, so that a ``remat`` policy may keep them: the forward then
    # runs once a layer
    y = saved_residual(y, RESIDUAL_SSD_Y)
    states = saved_residual(states, RESIDUAL_SSD_STATES)
    return y.reshape(x.shape), (x, dt, a, b, c, d, states)


def _ssd_scan_bwd(chunk, res, dy):
    x, dt, a, b, c, d, states = res
    arrays = _kernel_operands(x, dt, a, b, c, d)
    # a custom_vjp's backward is traced outside the forward's scope
    with jax.named_scope(SCOPE_SSD):
        dx, ddt, db, dc, da, dd = _scan_bwd(
            arrays, states, dy.reshape(arrays[0].shape).astype(x.dtype), chunk
        )
        return (
            dx.reshape(x.shape),
            jnp.swapaxes(ddt, 1, 2).astype(dt.dtype),
            jnp.sum(da[..., 0], axis=0).reshape(a.shape).astype(a.dtype),
            db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c.shape).astype(c.dtype),
            jnp.sum(dd[..., 0], axis=0).reshape(d.shape).astype(d.dtype),
        )


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)
