"""The gated delta rule: linear attention whose state is corrected, not
only added to, at every token (Gated DeltaNet; Yang, Kautz and
Hatamizadeh, arXiv:2412.06464), in the chunked form the MXU can run.

A head keeps a state ``S`` of ``K x V`` (``K`` key, ``V`` value
channels). At token ``t`` it decays the state by ``alpha_t = exp(g_t)``
(``g_t <= 0``, one number a head) and moves the state's answer for the
key ``k_t`` towards ``v_t`` by the step ``beta_t`` (the delta rule):

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

With unit-norm keys and ``beta`` in (0, 2) the eigenvalues of
``I - beta k k^T`` lie in (-1, 1]. Token by token that is ``T`` dependent
steps (:func:`gated_delta_rule_sequential`, the form the tests hold the
chunked one to).

:func:`gated_delta_rule_chunked` cuts the sequence into chunks of
``chunk`` tokens and carries only the state between them (the WY / UT
form of the delta rule's chunked parallel algorithm, Yang et al.,
arXiv:2406.06484). Inside a chunk, with ``gamma`` the running sum of
``g`` from the chunk's start (the token's own included) and
``Gamma_ij = exp(gamma_i - gamma_j)`` for ``i >= j``:

    A  = StrictLower(Diag(beta) (K K^T * Gamma))
    W  = (I + A)^-1 Diag(beta) (K * e^gamma)      U = (I + A)^-1 Diag(beta) V

and then, from the state ``S`` the chunk starts with,

    V' = U - W S
    O  = (Q * e^gamma) S + Lower(Q K^T * Gamma) V'
    S <- e^gamma_C S + (K * e^(gamma_C - gamma))^T V'

Every exponent is at most 0, so nothing overflows however strong the
decays. A length the chunk does not divide runs as if padded with tokens
of ``beta = 0`` and ``g = 0``, which neither decay nor feed the state,
and their outputs are dropped.

Two implementations of that one algorithm, chosen by what the input
shows (:func:`auto_picks_kernel`), never by a caller's option:

- the kernel pair ``delta_rule_fwd`` / ``delta_rule_bwd`` under one
  ``jax.custom_vjp`` (:func:`_delta_rule`), on a TPU wherever the
  blocking takes the shape (:func:`delta_rule_kernel_supported`), no
  initial state is given and the program may hold a kernel. A grid step
  is one (batch row, chunk), the chunks in order: it reads the chunk's
  q, k (C, H·K) and v (C, H·V) out of the arrays the mixer has them in,
  at the sequence's own length (a ragged last chunk is masked inside),
  and the running sums ``gamma`` and ``beta`` (C, H), the one re-layout
  (the chunk's running sum, 4 bytes a token a head, made in XLA). It
  copies each head's lanes into head-major VMEM scratch (H, C, ·) and
  walks the heads in groups (:func:`_group_size`), every product of a
  group batched over its heads. The state of every head, (H, K, V)
  float32, is a VMEM scratch over the chunk axis and never leaves the
  chip between chunks. ``(I + A)^-1`` is inverted by doubling the
  diagonal blocks it has inverted, ``T_2s = T_s - T_s (A * M_s) T_s``
  with ``M_s`` the lower-left ``s x s`` block of each ``2s`` block:
  block forward substitution, float32 products at HIGHEST, no
  ``triangular_solve``. The forward under differentiation also writes
  the state each chunk starts from and its ``(I + A)^-1`` (float32, the
  residuals ``gdn_states`` and ``gdn_inverse``, so that ``remat`` runs
  the forward once and the backward inverts nothing); the backward walks
  the chunks in reverse with the state's cotangent in VMEM, recomputes
  the rest of the UT transform from the inputs and writes dq, dk, dv,
  and d``gamma`` and dbeta a token a head; d``g`` is XLA's transpose of
  the running sum (a reverse running sum inside a chunk; the carried
  decay's term is at the chunk's last token);
- XLA's fusions over ``jax.numpy`` under autodiff (:func:`_chunked`),
  everywhere else: the CPU, a given initial state, a shape the blocking
  does not take, a partitioned program. A ragged length is padded and
  sliced off, the UT transform is a ``triangular_solve``, the state is
  carried by a ``lax.scan`` over the chunks, and the backward is JAX's
  autodiff through it all.

Precision, both: ``A``, its inverse (or solve), ``W`` and ``U``, the
decays, ``beta`` and the carried state are float32 (a bf16 inverse of a
64-row triangular matrix carries its rounding down the rows); the four
products with the state and ``V'`` read their operands in the inputs'
dtype and accumulate in float32; ``o`` is float32. In the backward the
transposes of those products, and of ``Q K^T``, meet a float32
cotangent (of ``o``, of the carried state, of ``V'``, of the scores)
with an operand in the inputs' dtype: XLA's autodiff multiplies the two
at HIGHEST, and the kernel's backward keeps the cotangent float32 there
too, in three bfloat16 pieces against a bfloat16 operand
(:func:`_split`), and the UT transform's products in float32. The whole
op, the kernel's backward too, runs under the scope ``delta_rule`` and is
counted once a trace under ``gdn.path.kernel`` or ``gdn.path.chunked``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from blendjax.ops.attention import _placement, shard_over_batch
from blendjax.utils.metrics import (
    KERNEL_DELTA_RULE_BWD,
    KERNEL_DELTA_RULE_FWD,
    RESIDUAL_GDN_INVERSE,
    RESIDUAL_GDN_STATES,
    SCOPE_DELTA_RULE,
    metrics,
    saved_residual,
)

_LANES = 128
# What a grid step's working set (_vmem_bytes) may come to: v5e's default
# scoped VMEM. The compiler is given twice that: in a fused step XLA may
# keep an operand of the kernel in VMEM, and the kernel then asks about
# 2 MiB more than alone (olmohybrid_replay's backward at 3 heads a group:
# 14.8 to 15.0 MiB alone, 16.9 to 17.0 in the step; tests/test_tpu_compile.py
# compiles both).
DELTA_VMEM_BYTES = 16 << 20

_einsum = functools.partial(
    jnp.einsum, precision=lax.Precision.HIGHEST,
    preferred_element_type=jnp.float32,
)


def gated_delta_rule_sequential(q, k, v, g, beta, initial_state=None):
    """The recurrence as written, one token at a time in float32: ``q``
    and ``k`` (B, T, H, K), ``v`` (B, T, H, V), ``g`` (B, T, H) the log
    of the decays, ``beta`` (B, T, H), ``initial_state`` (B, H, K, V) or
    zeros -> ``(o (B, T, H, V), final state (B, H, K, V))``, float32.
    For the tests."""
    bsz, _, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, dk, dv), jnp.float32)

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        state = jnp.exp(g_t)[..., None, None] * state
        said = _einsum("bhk,bhkv->bhv", k_t, state)
        state = state + k_t[..., None] * (
            b_t[..., None] * (v_t - said)
        )[..., None, :]
        return state, _einsum("bhk,bhkv->bhv", q_t, state)

    state, o = lax.scan(
        step, initial_state.astype(jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1), state


def delta_rule_kernel_supported(q, v, chunk: int) -> bool:
    """Whether the kernel pair's blocking takes ``q`` (B, T, H, K) and
    ``v`` (B, T, H, V) at this ``chunk``: a power of two of whole sublane
    tiles of the inputs' dtype (the inverse doubles blocks up to the
    chunk), ``K`` whole sublane tiles of the float32 state, and a grid
    step's working set within DELTA_VMEM_BYTES for some group of heads
    (:func:`_group_size`). ``H·K`` and ``H·V`` are whole blocks, any
    width."""
    if q.ndim != 4 or v.ndim != 4 or q.shape[:3] != v.shape[:3]:
        return False
    h, dk = q.shape[2:]
    size = jnp.dtype(q.dtype).itemsize
    return (
        chunk % (32 // size) == 0 and chunk & (chunk - 1) == 0
        and dk % 8 == 0 and _group_size(chunk, h, dk, v.shape[-1], size) > 0
    )


def _group_size(chunk: int, h: int, dk: int, dv: int, itemsize: int) -> int:
    """Heads a kernel batches at a time: the largest divisor of ``H``, at
    most 8, whose grid step of the backward (:func:`_vmem_bytes`) fits
    DELTA_VMEM_BYTES; 0 where none does."""
    fits = [g for g in range(1, min(h, 8) + 1) if h % g == 0 and _vmem_bytes(
        chunk, h, g, dk, dv, itemsize) <= DELTA_VMEM_BYTES]
    return max(fits, default=0)


def _vmem_bytes(chunk: int, h: int, g: int, dk: int, dv: int,
                itemsize: int) -> int:
    """What the chip's compiler asks of a grid step of the backward, the
    larger of the two: a buffer of each block (q, k, v and their
    gradients, do, the states and inverses the chunk starts from, gamma,
    beta and theirs, the final state's cotangent), the carried cotangent,
    the staged heads (q, k, v, dq, dk, dv and the float32 do), ``g``
    heads' float32 temporaries, about nine each of (C, C), (C, K) and (C,
    V), and the three bfloat16 pieces (:func:`_split`) of do, of V''s and
    of the carried cotangent, and 1 MiB of the compiler's own. (At
    olmohybrid_replay's shape, compiled for a described v5e: 14.8 to 15.0
    MiB at ``g`` 3 and 16.4 to 16.5 at 5; this says 15.2 and 18.1.)"""
    def lanes(n):
        return -(-n // _LANES) * _LANES

    state = h * dk * lanes(dv) * 4
    blocks = chunk * (2 * itemsize * (2 * lanes(h * dk) + lanes(h * dv))
                      + 4 * lanes(h * dv) + 4 * 4 * lanes(h))
    inverses = h * chunk * lanes(chunk) * 4
    staged = h * chunk * ((4 * lanes(dk) + 2 * lanes(dv)) * itemsize
                          + lanes(dv) * 4)
    temps = 4 * g * chunk * 9 * (lanes(chunk) + lanes(dk) + lanes(dv))
    pieces = 3 * 2 * g * (2 * chunk + dk) * lanes(dv)
    return (blocks + 3 * state + inverses + staged + temps + pieces
            + (1 << 20))


def auto_picks_kernel(q, v, chunk: int, initial_state=None) -> bool:
    """The ``auto`` policy: on a TPU, the kernel pair wherever its
    blocking takes the shape, no initial state is given, and the program
    being traced may hold a kernel (``ops/attention.py`` ``_placement``:
    a single-device program, or a declared mesh whose batch axes divide
    the batch, per shard); the XLA form everywhere else, which partitions
    like any other operation."""
    if (initial_state is not None or jax.default_backend() != "tpu"
            or not delta_rule_kernel_supported(q, v, chunk)):
        return False
    placed = _placement()
    return placed == "bare" or (
        placed is not None and q.shape[0] % placed[2] == 0
    )


def gated_delta_rule_chunked(q, k, v, g, beta, chunk: int = 64,
                             initial_state=None, backend: str = "auto"):
    """:func:`gated_delta_rule_sequential`'s result by chunks of
    ``chunk`` tokens (see the module): ``(o, final state)``, float32.

    ``backend``: ``"auto"`` (:func:`auto_picks_kernel`) | ``"xla"`` |
    ``"kernel"``, which raises on a shape the blocking does not take or
    a given initial state, and off a TPU runs the kernels in interpreter
    mode (the tests' way in)."""
    if backend not in ("auto", "xla", "kernel"):
        raise ValueError(f"unknown delta-rule backend {backend!r}")
    if backend == "kernel" and (
        initial_state is not None
        or not delta_rule_kernel_supported(q, v, chunk)
    ):
        raise ValueError(
            "delta-rule kernel requested but it does not take q "
            f"{q.shape} {q.dtype}, v {v.shape} at chunk {chunk}"
            + (" from a given state" if initial_state is not None else "")
            + ": the chunk a power of two of whole sublane tiles, K a "
            "multiple of 8, no initial state"
        )
    use_kernel = backend == "kernel" or (
        backend == "auto" and auto_picks_kernel(q, v, chunk, initial_state)
    )
    with jax.named_scope(SCOPE_DELTA_RULE):
        if not use_kernel:
            metrics.count("gdn.path.chunked")
            return _chunked(q, k, v, g, beta, chunk, initial_state)
        metrics.count("gdn.path.kernel")

        def rule(q, k, v, g, beta):
            return _by_kernels(q, k, v, g, beta, chunk)

        placed = _placement()
        if isinstance(placed, tuple):
            rule = shard_over_batch(rule, placed, q.shape[0], 5)
        return rule(q, k, v, g, beta)


def _chunked(q, k, v, g, beta, chunk, initial_state):
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    dtype = q.dtype
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    n = (t + pad) // chunk

    def chunks(x):  # (B, T, H, ...) -> (N, B, H, C, ...): chunks leading,
        # as the scan over them reads them
        x = x.reshape(bsz, n, chunk, h, -1)
        return x.transpose(1, 0, 3, 2, 4)

    q, k, v = (chunks(x) for x in (q, k, v))
    g, beta = (chunks(x.astype(jnp.float32))[..., 0] for x in (g, beta))
    gamma = jnp.cumsum(g, axis=-1)                        # (N, B, H, C)
    last = gamma[..., -1]                                 # (N, B, H)
    rows = jnp.arange(chunk)
    lower = rows[:, None] >= rows[None, :]
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
    ))                                                    # Gamma, 0 above

    # -- inside a chunk: the UT transform, float32 ---------------------------
    kf = k.astype(jnp.float32)
    a = beta[..., :, None] * _einsum("...ik,...jk->...ij", kf, kf) * decay
    a = jnp.where(rows[:, None] > rows[None, :], a, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    solved = lax.linalg.triangular_solve(
        eye + a, beta[..., None] * jnp.concatenate(
            [kf * jnp.exp(gamma)[..., None], v.astype(jnp.float32)], axis=-1
        ),
        left_side=True, lower=True, unit_diagonal=True,
    )
    w, u = solved[..., :dk], solved[..., dk:]
    to_end = k * jnp.exp(last[..., None] - gamma)[..., None]

    # -- between chunks: the carried state -------------------------------------
    def carry(state, inputs):
        w_n, u_n, to_end_n, last_n = inputs
        fresh = u_n - _einsum("bhck,bhkv->bhcv", w_n.astype(dtype),
                              state.astype(dtype))
        after = jnp.exp(last_n)[..., None, None] * state + _einsum(
            "bhck,bhcv->bhkv", to_end_n.astype(dtype), fresh.astype(dtype)
        )
        return after, (state, fresh)

    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, dk, dv), jnp.float32)
    final, (before, fresh) = lax.scan(
        carry, initial_state.astype(jnp.float32), (w, u, to_end, last)
    )
    scores = _einsum("...ik,...jk->...ij", q, k) * decay
    o = _einsum(
        "nbhck,nbhkv->nbhcv",
        (q.astype(jnp.float32) * jnp.exp(gamma)[..., None]).astype(dtype),
        before.astype(dtype),
    ) + _einsum("nbhcs,nbhsv->nbhcv", scores.astype(dtype), fresh.astype(dtype))
    o = o.transpose(1, 0, 3, 2, 4).reshape(bsz, n * chunk, h, dv)
    return o[:, :t], final


# -- the kernel pair -----------------------------------------------------------
#
# A grid step is one (batch row, chunk). Each head's lanes of the chunk's
# (C, H·K) and (C, H·V) blocks are copied into head-major scratch (H, C,
# ·) (:func:`_stage`), and a loop walks the heads in groups of G
# (:func:`_group_size`), every product of a group batched over its heads,
# so that G independent chains of small products keep the MXU fed.

_BNN = (((2,), (1,)), ((0,), (0,)))  # a[h] @ b[h]
_BNT = (((2,), (2,)), ((0,), (0,)))  # a[h] @ b[h].T
_BTN = (((1,), (1,)), ((0,), (0,)))  # a[h].T @ b[h]


def _dot(a, b, dims=_BNN):
    """A product accumulated in float32; float32 operands stay float32
    on the MXU (HIGHEST: six bf16 passes), as the XLA form's are. Either
    operand may be a float32 value in pieces (:func:`_split`): the sum of
    the pieces' products."""
    if isinstance(a, tuple):
        return sum(_dot(piece, b, dims) for piece in a)
    if isinstance(b, tuple):
        return sum(_dot(a, piece, dims) for piece in b)
    if a.dtype != b.dtype:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    precision = (lax.Precision.HIGHEST if a.dtype == jnp.float32 else None)
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _split(x, dtype):
    """A float32 cotangent for products with operands of ``dtype``, as
    :func:`_dot` takes it. For bfloat16, three bfloat16 pieces whose sum
    is ``x`` (8 significant bits each, float32's 24 in all), each one
    MXU pass against the bfloat16 operand: the XLA form's float32 x
    bfloat16 product at HIGHEST, whose other passes multiply the
    bfloat16 operand's lower pieces, all zero. Otherwise ``x`` whole."""
    if dtype != jnp.bfloat16:
        return x
    pieces = []
    for _ in range(3):
        pieces.append(x.astype(dtype))
        x = x - pieces[-1].astype(jnp.float32)
    return tuple(pieces)


def _rows(x):  # (G, M, N) -> (G, M, 1): the sum over each row
    return jnp.sum(x, axis=2, keepdims=True)


class _Masks:
    """A chunk's (C, C) masks: ``lower`` (the diagonal included),
    ``strict``, ``last`` (the chunk's last row, (C, 1)) and ``levels``,
    the lower-left ``s x s`` block of each ``2s`` block for ``s`` = 1, 2,
    ... C/2, with which the inverse doubles the blocks it has inverted."""

    def __init__(self, chunk: int):
        row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.eye = (row == col).astype(jnp.float32)
        self.lower = row >= col
        self.strict = row > col
        self.last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
        self.levels = []
        s = 1
        while s < chunk:
            self.levels.append(
                (row // (2 * s) == col // (2 * s)) & (row % (2 * s) >= s)
                & (col % (2 * s) < s)
            )
            s *= 2


def _unit_lower_inverse(a, masks):
    """``(I + a)^-1`` of a batch of strictly lower ``a``, float32: the
    inverses of its 2x2 diagonal blocks, then of its 4x4 ones from theirs,
    and so on (``T_2s = T_s - T_s (a * M_s) T_s``): block forward
    substitution, whose intermediates are inverses of leading blocks and
    stay as small as the result. (The product of powers of ``a``, exact
    in exact arithmetic too, sums terms that grow like binomials where
    keys repeat, as the stream's background patches do.)"""
    t = masks.eye - jnp.where(masks.levels[0], a, 0.0)
    for level in masks.levels[1:]:
        t = t - _dot(t, _dot(jnp.where(level, a, 0.0), t))
    return t


class _Sums:
    """A chunk's (C, H) running sums of ``g`` and steps ``beta``, whose
    columns and rows a group of heads takes by mask (a head is an index
    into them, never a lane offset)."""

    def __init__(self, gamma_ref, beta_ref):
        self.gamma, self.beta = gamma_ref[0], beta_ref[0]
        self.across = self.gamma.T                               # (H, C)
        self.lane = lax.broadcasted_iota(jnp.int32, self.gamma.shape, 1)
        self.sublane = lax.broadcasted_iota(jnp.int32, self.across.shape, 0)

    def columns(self, block, first, size):
        """Heads ``first`` to ``first + size`` of a (C, H) block: (G, C,
        1)."""
        return jnp.stack([
            jnp.sum(jnp.where(self.lane == first + i, block, 0.0), axis=1,
                    keepdims=True) for i in range(size)])

    def rows(self, first, size):
        """The heads' running sums across: (G, 1, C)."""
        return jnp.stack([jnp.sum(
            jnp.where(self.sublane == first + i, self.across, 0.0),
            axis=0, keepdims=True) for i in range(size)])


class _Heads:
    """What both kernels compute of heads ``first`` to ``first + G`` in a
    chunk before the state: the UT transform and the in-chunk scores, as
    the module writes them, each (G, C, ·), from the staged q, k, v (H,
    C, ·) and the chunk's sums; ``inverse``, ``(I + A)^-1`` where the
    forward kept it."""

    def __init__(self, staged, sums, first, size, masks, dk, inverse=None):
        from jax.experimental import pallas as pl

        at = pl.ds(first, size)
        c = staged[0].shape[1]
        self.q, self.k, self.v = (x[at] for x in staged)
        self.dtype = self.q.dtype
        self.gamma = sums.columns(sums.gamma, first, size)       # (G, C, 1)
        self.beta = sums.columns(sums.beta, first, size)
        self.decay = jnp.exp(jnp.where(
            masks.lower, self.gamma - sums.rows(first, size), -jnp.inf))
        last = self.gamma[:, c - 1:c]                            # (G, 1, 1)
        self.whole = jnp.exp(jnp.broadcast_to(last, (size, dk, 1)))
        self.e = jnp.exp(self.gamma)
        self.to_end = jnp.exp(
            jnp.broadcast_to(last, self.gamma.shape) - self.gamma)
        self.k32 = self.k.astype(jnp.float32)
        self.kk = _dot(self.k, self.k, _BNT)                     # f32
        self.a = jnp.where(masks.strict, self.beta * self.kk * self.decay, 0.0)
        self.t = (_unit_lower_inverse(self.a, masks) if inverse is None
                  else inverse)
        self.rk = self.beta * (self.k32 * self.e)
        self.v32 = self.v.astype(jnp.float32)
        self.w = _dot(self.t, self.rk)
        self.u = _dot(self.t, self.beta * self.v32)
        self.scores = _dot(self.q, self.k, _BNT) * self.decay
        self.qe = self.q.astype(jnp.float32) * self.e
        self.kr = self.k32 * self.to_end


def _stage(blocks, scratch, index, t):
    """A grid step's chunk, head-major: each head's slice of the (1, C,
    H·width) ``blocks`` into the (H, C, width) ``scratch``, so that a
    loop over groups of heads indexes heads, never lanes. The rows past
    ``t`` are zeroed (what a ragged last block's copy left there is
    unspecified)."""
    c = blocks[0].shape[1]
    valid = None
    if t % c:
        valid = index * c + lax.broadcasted_iota(jnp.int32, (c, 1), 0) < t
    for ref, into in zip(blocks, scratch):
        width = into.shape[2]
        for h in range(into.shape[0]):
            x = ref[0, :, h * width:(h + 1) * width]
            if valid is not None:
                x = jnp.where(valid, x, jnp.zeros_like(x))
            into[h] = x.astype(into.dtype)


def _unstage(scratch, ref):
    """(H, C, width) scratch -> the heads' lanes of a (1, C, H·width)
    block."""
    width = scratch.shape[2]
    for h in range(scratch.shape[0]):
        ref[0, :, h * width:(h + 1) * width] = scratch[h].astype(ref.dtype)


def _rule_fwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, o_ref,
                     final_ref, *rest, t, group_size):
    """``rest``: the outputs of the states the chunks start from and of
    their ``(I + A)^-1``, where the backward will want them, then the
    scratch: the carried state (H, K, V), the staged q, k, v and the
    output (H, C, ·)."""
    from jax.experimental import pallas as pl

    residuals, (state, qs, ks, vs, os_) = rest[:-5], rest[-5:]
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if residuals:
        residuals[0][0, 0] = state[...]
    h, dk, dv = state.shape
    _stage((q_ref, k_ref, v_ref), (qs, ks, vs), n, t)
    sums, masks = _Sums(gamma_ref, beta_ref), _Masks(q_ref.shape[1])

    def group(j, carry):
        first = pl.multiple_of(j * group_size, group_size)
        at = pl.ds(first, group_size)
        hd = _Heads((qs, ks, vs), sums, first, group_size, masks, dk)
        if residuals:
            residuals[1][0, 0, at] = hd.t
        dtype = hd.dtype
        s = state[at]                                            # (G, K, V)
        read = s.astype(dtype)
        fresh = (hd.u - _dot(hd.w.astype(dtype), read)).astype(dtype)
        os_[at] = _dot(hd.qe.astype(dtype), read) + _dot(
            hd.scores.astype(dtype), fresh)
        state[at] = hd.whole * s + _dot(hd.kr.astype(dtype), fresh, _BTN)
        return carry

    lax.fori_loop(0, h // group_size, group, 0)
    _unstage(os_, o_ref)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        final_ref[0] = state[...]


def _rule_bwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, st_ref,
                     inv_ref, do_ref, dfinal_ref, dq_ref, dk_ref, dv_ref,
                     dgamma_ref, dbeta_ref,
                     dstate, qs, ks, vs, dos, dqs, dks, dvs, *, t, group_size):
    """One chunk's part of every gradient, the chunks in reverse: the
    cotangent of the state a chunk leaves is in ``dstate`` when the
    chunk runs, that of the state it starts from when it is done. The
    staged q, k, v, do and the gradients are (H, C, ·) scratch."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dstate[...] = dfinal_ref[0]

    n = pl.num_programs(1) - 1 - i
    h, dk, dv = dstate.shape
    _stage((q_ref, k_ref, v_ref, do_ref), (qs, ks, vs, dos), n, t)
    sums, masks = _Sums(gamma_ref, beta_ref), _Masks(q_ref.shape[1])

    def group(j, outs):
        dgamma_out, dbeta_out = outs
        first = pl.multiple_of(j * group_size, group_size)
        at = pl.ds(first, group_size)
        hd = _Heads((qs, ks, vs), sums, first, group_size, masks, dk,
                    inverse=inv_ref[0, 0, at])
        dtype = hd.dtype
        # the cotangents stay float32 where they meet a product, as in the
        # XLA form's autodiff
        do = _split(dos[at], dtype)                              # (G, C, V)
        s = st_ref[0, 0, at]                                     # (G, K, V)
        read = s.astype(dtype)
        dnext = dstate[at]
        dnext_pieces = _split(dnext, dtype)
        fresh = (hd.u - _dot(hd.w.astype(dtype), read)).astype(dtype)
        # the entering state's read-out, O += (Q e^gamma) S, and the
        # update, S' = e^gamma_C S + (K e^(gamma_C - gamma))^T V'
        dqe = _dot(do, read, _BNT)
        dkr = _dot(fresh, dnext_pieces, _BNT)
        # inside the chunk: O += Lower(Q K^T * Gamma) V'
        dp = jnp.where(masks.lower, _dot(do, fresh, _BNT), 0.0)
        d_fresh = _dot(hd.kr.astype(dtype), dnext_pieces) + _dot(
            hd.scores.astype(dtype), do, _BTN)
        d_fresh_pieces = _split(d_fresh, dtype)
        # V' = U - W S
        dw = -_dot(d_fresh_pieces, read, _BNT)
        dstate[at] = hd.whole * dnext + _dot(
            hd.qe.astype(dtype), do, _BTN) - _dot(
            hd.w.astype(dtype), d_fresh_pieces, _BTN)
        # the UT transform, float32: [W | U] = T R, T = (I + A)^-1
        drk = _dot(hd.t, dw, _BTN)
        drv = _dot(hd.t, d_fresh, _BTN)
        da = jnp.where(masks.strict,
                       -(_dot(drk, hd.w, _BNT) + _dot(drv, hd.u, _BNT)), 0.0)
        dkk = da * hd.beta * hd.decay
        dqk = _split(dp * hd.decay, dtype)
        mixed = da * hd.a + dp * hd.scores   # d(exp(gamma_i - gamma_j))
        dqs[at] = (_dot(dqk, hd.k) + dqe * hd.e).astype(dqs.dtype)
        dks[at] = (drk * hd.beta * hd.e
                   + _dot(dkk + jnp.swapaxes(dkk, 1, 2), hd.k32)
                   + _dot(dqk, hd.q, _BTN) + dkr * hd.to_end
                   ).astype(dks.dtype)
        dvs[at] = (drv * hd.beta).astype(dvs.dtype)
        dbeta = (_rows(drk * hd.k32 * hd.e) + _rows(drv * hd.v32)
                 + _rows(da * hd.kk * hd.decay))
        to_end = _rows(dkr * hd.kr)
        # the chunk's last running sum: the carried decay and every
        # token's decay to the chunk's end
        dlast = hd.whole[:, :1] * jnp.sum(
            _rows(s * dnext), axis=1, keepdims=True) + jnp.sum(
            to_end, axis=1, keepdims=True)                       # (G, 1, 1)
        dgamma = (_rows(drk * hd.rk) + _rows(dqe * hd.qe) - to_end
                  + _rows(mixed) + jnp.where(
                      masks.last, jnp.broadcast_to(dlast, to_end.shape), 0.0))
        across = -jnp.sum(mixed, axis=1, keepdims=True)          # (G, 1, C)
        for g in range(group_size):
            head = sums.lane == first + g
            dgamma_out = jnp.where(head, dgamma[g] + across[g].T, dgamma_out)
            dbeta_out = jnp.where(head, dbeta[g], dbeta_out)
        return dgamma_out, dbeta_out

    zeros = jnp.zeros(gamma_ref.shape[1:], jnp.float32)
    dgamma_ref[0], dbeta_ref[0] = lax.fori_loop(
        0, h // group_size, group, (zeros, zeros))
    for scratch, ref in ((dqs, dq_ref), (dks, dk_ref), (dvs, dv_ref)):
        _unstage(scratch, ref)


def _launch(q, v, gamma, chunk, reverse):
    """The kernels' static arguments, then grid, scratch and compiler
    parameters, block specs by kind and the shapes of what only the
    kernels make, for one of the pair over q (B, T, H·K), v (B, T, H·V)
    and gamma (B, T', H), T' the chunks' whole length. ``reverse``: the
    grid's chunk axis walks the chunks last to first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, hk = q.shape
    h = gamma.shape[2]
    dk, dv = hk // h, v.shape[2] // h
    nc = -(-t // chunk)

    def at(n):
        return nc - 1 - n if reverse else n

    once = dict(pipeline_mode=pl.Buffered(1))  # a block per batch row
    specs = {
        "qk": pl.BlockSpec((1, chunk, hk), lambda b, n: (b, at(n), 0)),
        "v": pl.BlockSpec((1, chunk, h * dv), lambda b, n: (b, at(n), 0)),
        "heads": pl.BlockSpec((1, chunk, h), lambda b, n: (b, at(n), 0)),
        "states": pl.BlockSpec((1, 1, h, dk, dv),
                               lambda b, n: (b, at(n), 0, 0, 0)),
        "inverses": pl.BlockSpec((1, 1, h, chunk, chunk),
                                 lambda b, n: (b, at(n), 0, 0, 0)),
        "final": pl.BlockSpec((1, h, dk, dv), lambda b, n: (b, 0, 0, 0),
                              **once),
    }
    shapes = {
        "states": jax.ShapeDtypeStruct((bsz, nc, h, dk, dv), jnp.float32),
        "inverses": jax.ShapeDtypeStruct((bsz, nc, h, chunk, chunk),
                                         jnp.float32),
        "final": jax.ShapeDtypeStruct((bsz, h, dk, dv), jnp.float32),
    }
    static = dict(t=t, group_size=_group_size(
        chunk, h, dk, dv, jnp.dtype(q.dtype).itemsize))
    dtype = q.dtype
    staged = [pltpu.VMEM((h, chunk, dk), dtype)] * 2 + [
        pltpu.VMEM((h, chunk, dv), dtype)]
    if reverse:  # do, float32, then dq, dk, dv
        staged += [pltpu.VMEM((h, chunk, dv), jnp.float32)] + staged
    else:        # o
        staged += [pltpu.VMEM((h, chunk, dv), jnp.float32)]
    return static, dict(
        grid=(bsz, nc),
        scratch_shapes=[pltpu.VMEM((h, dk, dv), jnp.float32), *staged],
        # the carried state is a scratch over the chunk axis: a batch
        # row's chunks run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * DELTA_VMEM_BYTES,
        ),
        interpret=jax.default_backend() != "tpu",
    ), specs, shapes


_INPUTS = ("qk", "qk", "v", "heads", "heads")


# Both launches are jitted: the model's layers call them with the same
# shapes, and the kernel's body is then traced once a program and not
# once a layer and pass.
@functools.partial(jax.jit, static_argnames=("chunk", "for_backward"))
def _rule_fwd(arrays, chunk, for_backward):
    """o (B, T, H·V) float32, the final state (B, H, K, V) and, with
    ``for_backward``, the state each chunk starts from (B, N, H, K, V) and
    its ``(I + A)^-1`` (B, N, H, C, C)."""
    from jax.experimental import pallas as pl

    q, _, v, gamma, _ = arrays
    static, call, specs, shapes = _launch(q, v, gamma, chunk, reverse=False)
    outs = [(specs["v"], jax.ShapeDtypeStruct(v.shape, jnp.float32)),
            (specs["final"], shapes["final"])]
    if for_backward:
        outs += [(specs[k], shapes[k]) for k in ("states", "inverses")]
    return pl.pallas_call(
        functools.partial(_rule_fwd_kernel, **static),
        in_specs=[specs[k] for k in _INPUTS],
        out_specs=[spec for spec, _ in outs],
        out_shape=[shape for _, shape in outs],
        name=KERNEL_DELTA_RULE_FWD, **call,
    )(*arrays)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _rule_bwd(arrays, states, inverses, do, dfinal, chunk):
    """dq, dk, dv in the inputs' dtype, dgamma and dbeta (B, T', H)
    float32."""
    from jax.experimental import pallas as pl

    q, k, v, gamma, beta = arrays
    static, call, specs, _ = _launch(q, v, gamma, chunk, reverse=True)
    outs = [(specs["qk"], q), (specs["qk"], k), (specs["v"], v),
            (specs["heads"], gamma), (specs["heads"], beta)]
    return pl.pallas_call(
        functools.partial(_rule_bwd_kernel, **static),
        in_specs=[specs[k] for k in
                  _INPUTS + ("states", "inverses", "v", "final")],
        out_specs=[spec for spec, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for _, x in outs],
        name=KERNEL_DELTA_RULE_BWD, **call,
    )(*arrays, states, inverses, do, dfinal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _delta_rule(q, k, v, gamma, beta, chunk):
    """The chunked rule as the kernel pair over q, k (B, T, H·K), v (B, T,
    H·V) and the chunks' running sums of g and beta (B, T', H) float32:
    ``(o (B, T, H·V), final state (B, H, K, V))``, float32."""
    return tuple(_rule_fwd((q, k, v, gamma, beta), chunk, for_backward=False))


def _delta_rule_fwd(q, k, v, gamma, beta, chunk):
    o, final, states, inverses = _rule_fwd((q, k, v, gamma, beta), chunk,
                                           for_backward=True)
    # named, so that a ``remat`` policy may keep them: the forward then
    # runs once a layer
    states = saved_residual(states, RESIDUAL_GDN_STATES)
    inverses = saved_residual(inverses, RESIDUAL_GDN_INVERSE)
    return (o, final), (q, k, v, gamma, beta, states, inverses)


def _delta_rule_bwd(chunk, res, cotangents):
    q, k, v, gamma, beta, states, inverses = res
    do, dfinal = cotangents
    # a custom_vjp's backward is traced outside the forward's scope
    with jax.named_scope(SCOPE_DELTA_RULE):
        return tuple(_rule_bwd((q, k, v, gamma, beta), states, inverses,
                               do.astype(jnp.float32),
                               dfinal.astype(jnp.float32), chunk))


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def _by_kernels(q, k, v, g, beta, chunk):
    """The kernel pair on the mixer's (B, T, H, ·) arrays: heads folded
    into lanes, which is a reshape; the running sum of ``g`` inside each
    chunk made here (autodiff's transpose of it gives ``dg``), it and
    ``beta`` float32 and padded to whole chunks with zeros."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = ((0, 0), (0, -t % chunk), (0, 0))
    g, beta = (jnp.pad(x.astype(jnp.float32), pad) for x in (g, beta))
    gamma = jnp.cumsum(g.reshape(bsz, -1, chunk, h), axis=2).reshape(g.shape)
    o, final = _delta_rule(
        q.reshape(bsz, t, h * dk), k.reshape(bsz, t, h * dk),
        v.reshape(bsz, t, h * dv), gamma, beta, chunk,
    )
    return o.reshape(bsz, t, h, dv), final
