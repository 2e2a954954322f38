"""Local (single-device) attention backends.

Net-new vs the reference (blendtorch has no sequence models, SURVEY.md
§2.4). Two exact backends behind one call:

- ``xla``: :func:`blendjax.parallel.ring.reference_attention` — plain
  einsum attention with bf16 MXU matmuls, f32 score accumulation, and
  f32 softmax. Materializes the (B, H, T, T) score tensor in HBM.
- ``flash``: the repo's own fused kernel (:func:`_flash_core`) for
  "one head's K and V fit in VMEM", which up to 16k keys they do: a
  grid step takes a block of query rows against all keys, so the
  softmax is one pass (no running max, no rescale), and the backward
  is one kernel of five matmuls that recomputes the scores from the
  saved log-sum-exp. No (B, H, T, T) tensor reaches HBM, forward or
  backward. It works on (B, T, H·D), the layout the projections
  around it read and write, 128 lanes — 128 // D whole heads — a
  block. For self-attention from one projection nothing is transposed
  or copied either side of it (:func:`local_attention_packed`): the
  ``qkv`` product, written flat as (B, T, 3·H·D), is the kernels' one
  operand — q, k and v are column blocks of it, a third of its width
  apart — and the backward writes one gradient of that shape, which
  the projection's transposes read as it is, with the bias's gradient
  (its column sums) beside it. Three separate tensors
  (:func:`local_attention`: ulysses' per-device body, cross-attention)
  run the same kernels, and XLA copies each into and out of the
  row-major layout a custom call takes where its own layout differs
  (six copies a layer in ``vit_b16`` until PR 35: 1.38 ms an update,
  and 0.79 more in the slices that fed them). Any T, at its own length: K/V are
  padded to the score tile's 128 lanes in VMEM, inside the kernel
  (padded keys masked), and Q runs unpadded wherever a block of whole
  sublane tiles divides T (1,200 tokens run as 1,200; no pad and no
  slice in HBM). Only a T no such block divides (197) pads Q in HBM.
  The same precision as ``xla``: input-dtype MXU operands, f32
  accumulation, f32 max/sum/exp.

``auto`` policy: one algorithm that wants a different path by size, so
it reads the bytes of f32 scores a materialised call would write
(:func:`scores_residual_bytes`) on a TPU. Measured on one TPU v5e
(my chip runs, PR 26, the last two rows again in PR 33 with today's
geometry; ``scripts/attn_core_time.py``: the core alone, forward +
backward, bf16, 12 chained calls a dispatch, ms a call):

====================  ============  ======  ======  =========
shape (B, T, H, D)    score bytes   xla     flash   xla/flash
====================  ============  ======  ======  =========
(8, 197, 12, 64)      14.9 MB       0.112   0.157   0.71
(8, 256, 12, 64)      25.2 MB       0.140   0.132   1.06
(8, 512, 4, 128)      33.6 MB       0.213   0.128   1.67
(8, 320, 12, 64)      39.3 MB       0.343   0.251   1.37
(8, 384, 12, 64)      56.6 MB       0.549   0.213   2.58
(8, 768, 4, 128)      75.5 MB       0.871   0.253   3.4
(8, 1200, 12, 64)     553 MB        8.960   1.628   5.5
(4, 3072, 4, 128)     604 MB        9.100   1.675   5.4
====================  ============  ======  ======  =========

The materialised path sits on the HBM's bandwidth (PERF.md §5: 84 % of
819 GB/s at 1,200 tokens) as soon as the scores are streamed; below
that the kernel's fixed costs lose (197 tokens pad to 256). The four
shapes ISSUE 26 named put the crossover between 15 and 75 MB, the four
between them at 15–25 MB, so :data:`FLASH_RESIDUAL_BYTES` is 24 MiB:
every measured win is kept, the measured loss avoided, and ViT at 224
px and the 64-token rehearsal stay on XLA. (Both paths' costs grow
with B·H, so what decides is T: a batch of 16 or more at 197 tokens
passes the bar and should not. The bytes are what ISSUE 26 asked the
policy to read; PERF.md §7.) Kernels tried at the benchmark's shape on
the way (the same run kind, ms a call): the upstream
``jax.experimental.pallas.ops.tpu.flash_attention`` with pad + segment
ids 15.8 at its default 128 blocks — what the old "in-model the
materialised path keeps winning" note had measured — and 3.94 at the
best of seven block choices (640/640/640); this kernel's first version
on head-major (B, H, T, D) operands 2.07 alone, but 24.5 ms an update
in the model against this one's 21.3 (19.9 since PR 33 runs 1,200
tokens as 1,200), and 7.6 ms more outside the core, for the
transposes it forced on its neighbours. Explicit
``backend="flash"`` always takes the kernel.

Under a multi-device mesh the kernel is a custom call GSPMD cannot
partition: the step builders declare their mesh while the model is
traced (:func:`batch_sharded_over`) and the kernel runs per batch
shard through ``shard_map``. Where several devices are present and
nothing is declared, ``auto`` cannot know the program is single-device
and keeps ``xla`` (:func:`_placement`).

The sequence-parallel kernels (:mod:`blendjax.parallel.ring`,
:mod:`blendjax.parallel.ulysses`) shard T across devices *before* any
local attention runs; this module is the per-device math below them.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from blendjax.parallel.ring import reference_attention
from blendjax.utils.metrics import (
    KERNEL_FLASH_BWD,
    KERNEL_FLASH_FWD,
    SCOPE_ATTN_CORE,
    metrics,
)

# `auto` takes the fused kernel when one call would otherwise write at
# least this many bytes of f32 scores to HBM (`scores_residual_bytes`).
# Set from the measurement in the module docstring.
FLASH_RESIDUAL_BYTES = 24 << 20
# The kernel keeps one head's K and V in VMEM whole and works on
# [block_q, padded_kv] score tiles of at most FLASH_TILE_ELEMS. Measured
# at (8, 1200, 12, 64) with ``scripts/attn_core_time.py --block-q`` (my
# chip run, PR 33; ms a call): unpadded blocks of 240 / 400 / 600 /
# 1,200 rows 1.722 / 1.770 / 1.662 / 1.629, PR 26's 640 rows on Q
# padded to 1,280 1.857. One block of 1,200 wins, so the bound admits
# it; 400 loses to 240 because the backward's dK and dV contract over
# the block's rows and the MXU runs that in 128s (400 as 512). At
# (4, 3072, 4, 128) blocks of 256 / 512 / 768 rows took 1.687 / 1.673 /
# 1.673. FLASH_MAX_KV (with a 128-row block, the same tile) is what the
# chip's compiler accepts under FLASH_VMEM_BYTES of the v5e's 128 MiB
# (tests/test_tpu_compile.py).
FLASH_MAX_KV = 16384
FLASH_TILE_ELEMS = 1 << 21
FLASH_VMEM_BYTES = 100 << 20
_LANES = 128
# exp(_MASK - lse) is exactly 0 and _MASK - _MASK is not NaN
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _sublanes(dtype) -> int:
    """Rows of one (sublane, 128) tile of ``dtype``: 8 for f32, 16 for
    bf16. A block of whole tiles is what the chip's compiler takes
    without a pad."""
    return 32 // jnp.dtype(dtype).itemsize


class FlashBlocks(NamedTuple):
    """Launch geometry of one fused call: query rows a grid step, the
    rows Q has in HBM (``t_q`` itself where a block divides it) and the
    keys of a score tile (K/V in VMEM, a multiple of 128 lanes)."""

    block_q: int
    padded_q: int
    padded_kv: int


def flash_block_sizes(t_q: int, t_kv: int, dtype=jnp.bfloat16) -> FlashBlocks:
    """Launch geometry from the shape — the one source of truth for
    eligibility (:func:`flash_supported`) and launch. K/V stay in VMEM
    whole, padded there to the next multiple of 128 (the score tile's
    lanes). The query block is the largest divisor of ``t_q`` made of
    whole sublane tiles of ``dtype`` whose [block_q, padded_kv] score
    tile stays under FLASH_TILE_ELEMS, so Q is not padded at all
    (1,200 rows: one block; 3,072: six of 512), if it is ``t_q`` itself
    or at least 128 rows; where ``t_q`` has no such divisor (197, 130;
    600 in bf16), the largest multiple of 128 under the bound, shrunk
    so the blocks pad ``t_q`` as little as they can."""
    t_q, padded_kv = int(t_q), _round_up(t_kv, _LANES)
    cap = max(_LANES, FLASH_TILE_ELEMS // padded_kv)
    tile = _sublanes(dtype)
    exact = max(
        (b for b in range(tile, min(cap, t_q) + 1, tile) if t_q % b == 0),
        default=0,
    )
    if exact == t_q or exact >= _LANES:
        return FlashBlocks(exact, t_q, padded_kv)
    n = -(-t_q // (cap // _LANES * _LANES))
    block_q = _round_up(-(-t_q // n), _LANES)
    return FlashBlocks(block_q, n * block_q, padded_kv)


def scores_residual_bytes(q, k=None) -> int:
    """Bytes of f32 scores one materialised call writes to HBM and keeps
    for its backward pass — the policy's input, and the term that makes
    materialised attention infeasible at long context
    (``reference_attention`` normalises the probabilities in f32 and
    casts only at the output matmul)."""
    b, tq, h, _ = q.shape
    tk = q.shape[1] if k is None else k.shape[1]
    return b * h * tq * tk * 4


def flash_supported(q, k=None) -> bool:
    """Whether the fused kernel can take these (B, T, H, D) inputs: any
    sequence length (K/V padded and masked inside the kernel, Q padded
    around it where no block divides it) whose K/V stay in VMEM, and
    heads that fill 128-lane blocks whole
    (D = 128, or a divisor of it with H a multiple of 128 // D)."""
    k = q if k is None else k
    if not (q.ndim == 4 and k.ndim == 4):
        return False
    h, d = q.shape[2:]
    return (
        flash_block_sizes(q.shape[1], k.shape[1]).padded_kv <= FLASH_MAX_KV
        and _LANES % d == 0 and (h * d) % _LANES == 0
    )


# The program being traced, as its step builder declared it: (mesh,
# batch axes). The kernel is a custom call GSPMD cannot partition, so
# under a mesh it runs per shard through shard_map.
_PROGRAM_MESH = contextvars.ContextVar("attn_program_mesh", default=None)


@contextlib.contextmanager
def batch_sharded_over(mesh, data_axis: str = "data"):
    """Declare, while a model is traced, that the program runs on
    ``mesh`` with the batch on ``data_axis``
    (``make_fused_tile_step(mesh=)`` and the mesh step builders do).
    ``mesh=None`` declares nothing."""
    if mesh is None:
        yield
        return
    token = _PROGRAM_MESH.set((mesh, data_axis))
    try:
        yield
    finally:
        _PROGRAM_MESH.reset(token)


def _placement():
    """How the kernel may be called in the program being traced:

    - ``"bare"``: a single-device program (one device in the process,
      or a declared one-device mesh), or the per-device body of a
      ``shard_map`` region (ulysses);
    - ``(mesh, batch axes, n)``: a declared multi-device mesh — through
      ``shard_map`` over the axes, which cut the batch ``n`` ways;
    - ``None``: several devices and nothing declared. The program may
      be partitioned (a ``jax.jit`` with shardings, a model's init on a
      mesh), where the lowering refuses a bare kernel: ``auto`` keeps
      the XLA path, an explicit ``flash`` calls bare.
    """
    if jax.sharding.get_abstract_mesh().manual_axes:
        return "bare"
    declared = _PROGRAM_MESH.get()
    if declared is None:
        return "bare" if jax.device_count() == 1 else None
    mesh, axes = declared
    if mesh.size == 1:
        return "bare"
    axes = tuple(
        a for a in ((axes,) if isinstance(axes, str) else axes or ())
        if a in mesh.shape
    )
    return mesh, axes, math.prod(mesh.shape[a] for a in axes)


def auto_picks_flash(q, k=None) -> bool:
    """The ``auto`` policy, exposed so callers (the bench's longseq
    row) can report which backend a shape resolves to: on a TPU, the
    fused kernel from FLASH_RESIDUAL_BYTES of scores up, where it can
    run — in a program known to be single-device, and under a declared
    mesh whose batch axes divide the batch (else XLA's path, which
    partitions like any other op)."""
    if jax.default_backend() != "tpu" or not flash_supported(q, k):
        return False
    if scores_residual_bytes(q, k) < FLASH_RESIDUAL_BYTES:
        return False
    placed = _placement()
    return placed == "bare" or (
        placed is not None and q.shape[0] % placed[2] == 0
    )


def _scores(q, k, *, scale, t_kv, causal, row0):
    """[block_q, padded_kv] f32 scores of one query block: scaled,
    padded keys (and the future, if causal) at _MASK."""
    s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    s = s * scale
    cols = lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
    if t_kv < s.shape[1]:
        s = s + jnp.where(cols < t_kv, 0.0, _MASK)
    if causal:
        rows = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(cols <= rows, s, _MASK)
    return s


# A block is 128 lanes of the (B, T, H·D) layout the projections write:
# 128 // D whole heads side by side. A head's operand is the block with
# the other heads' lanes zeroed — a contraction over 128 lanes, half of
# them zeros, costs the MXU what a contraction over 64 does — and a
# head's result is valid in its own lanes of the [rows, 128] product.


def _head_lanes(d: int):
    """One [1, 128] lane mask a head of the block; ``[None]`` for a
    single 128-wide head, which needs none."""
    if d == _LANES:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return [(lane >= h * d) & (lane < (h + 1) * d)
            for h in range(_LANES // d)]


def _only(x, lanes):
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _merge(parts, heads):
    """Per-head [rows, 128] results, each valid in its head's lanes."""
    out = parts[0]
    for part, lanes in zip(parts[1:], heads[1:]):
        out = jnp.where(lanes, part, out)
    return out


def _kv_in_vmem(k_ref, v_ref, pads, first):
    """One head block's K and V as [padded_kv, 128] values. Where
    ``t_kv`` does not fill the score tile's lanes, the first grid step
    of a (batch, head block) copies them into the ``pads`` scratch and
    zeroes its tail: the padding is made here, not in HBM. (A padded
    key is masked, but its product must be finite.)"""
    from jax.experimental import pallas as pl

    if not pads:
        return k_ref[0], v_ref[0]
    t_kv = k_ref.shape[1]

    @pl.when(first)
    def _():
        for ref, pad in zip((k_ref, v_ref), pads):
            pad[:t_kv] = ref[0]
            pad[t_kv:] = jnp.zeros((pad.shape[0] - t_kv, _LANES), pad.dtype)

    return pads[0][...], pads[1][...]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *kv_pads, d, scale,
                causal):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    q = q_ref[0]
    k, v = _kv_in_vmem(k_ref, v_ref, kv_pads, i == 0)
    heads = _head_lanes(d)
    outs, lses = [], []
    for lanes in heads:
        s = _scores(_only(q, lanes), k, scale=scale, t_kv=k_ref.shape[1],
                    causal=causal, row0=i * q.shape[0])
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        o = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        outs.append(o / l)
        lses.append(jnp.broadcast_to(m + jnp.log(l), q.shape))
    o_ref[0] = _merge(outs, heads).astype(o_ref.dtype)
    lse_ref[0] = _merge(lses, heads)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *rest, d, scale,
                causal, packed):
    """One query block's part of dQ, dK and dV. ``rest``: the outputs,
    the f32 dK/dV accumulators and the K/V padding scratch. With three
    tensors the outputs are ``dq`` (a block of rows), ``dk`` and ``dv``
    (whole). ``packed``: one array shaped like the ``qkv`` product, a
    batch row of it resident over the head blocks and query blocks —
    each gradient is stored at its own column block of it, so the
    array leaves the kernel as the projection's transposes read it —
    and the column sums of what is stored there (the projection's bias
    gradient, for the price of a sublane reduction)."""
    from jax.experimental import pallas as pl

    j, i = pl.program_id(1), pl.program_id(2)
    if packed:
        dqkv_ref, sums_ref, dk_acc, dv_acc, *kv_pads = rest
        head_blocks = dqkv_ref.shape[2] // (3 * _LANES)

        @pl.when((j == 0) & (i == 0))
        def _():
            sums_ref[...] = jnp.zeros_like(sums_ref)
    else:
        dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *kv_pads = rest

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0]
    k, v = _kv_in_vmem(k_ref, v_ref, kv_pads, i == 0)
    o, lse = o_ref[0].astype(jnp.float32), lse_ref[0]
    heads = _head_lanes(d)
    dqs = []
    for h, lanes in enumerate(heads):
        qh, doh = _only(q, lanes), _only(do_ref[0], lanes)
        s = _scores(qh, k, scale=scale, t_kv=k_ref.shape[1], causal=causal,
                    row0=i * q.shape[0])
        p = jnp.exp(s - lse[:, h * d:h * d + 1])
        dp = lax.dot_general(doh, v, _NT, preferred_element_type=jnp.float32)
        di = jnp.sum(o * doh.astype(jnp.float32), axis=1, keepdims=True)
        ds = (p * (dp - di) * scale).astype(q.dtype)
        p = p.astype(doh.dtype)
        dqs.append(jnp.dot(ds, k, preferred_element_type=jnp.float32))
        # zero outside the head's lanes, so the heads add up in place
        dv_acc[...] += lax.dot_general(
            p, doh, _TN, preferred_element_type=jnp.float32
        )
        dk_acc[...] += lax.dot_general(
            ds, qh, _TN, preferred_element_type=jnp.float32
        )
    dq, t_kv = _merge(dqs, heads), k_ref.shape[1]
    last = i == pl.num_programs(2) - 1
    if not packed:
        dq_ref[0] = dq.astype(dq_ref.dtype)

        @pl.when(last)
        def _():
            dk_ref[0] = dk_acc[:t_kv].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:t_kv].astype(dv_ref.dtype)

        return

    def store(part, grad, rows=slice(None)):
        """``grad`` into q's, k's or v's (``part`` 0, 1, 2) column
        block of this head block, and its column sums beside it."""
        cols = pl.ds(
            pl.multiple_of((part * head_blocks + j) * _LANES, _LANES), _LANES
        )
        dqkv_ref[0, rows, cols] = grad.astype(dqkv_ref.dtype)
        sums_ref[0, :, cols] += jnp.sum(grad, axis=0, keepdims=True)

    block_q = q.shape[0]
    store(0, dq, slice(None) if block_q == dqkv_ref.shape[1] else pl.ds(
        pl.multiple_of(i * block_q, block_q), block_q))

    @pl.when(last)
    def _():
        store(1, dk_acc[:t_kv])
        store(2, dv_acc[:t_kv])


class _Launch(NamedTuple):
    """Block specs, grid and scratch of one launch over (B, T, ·)
    operands, 128 lanes — one head block — a grid step."""

    grid: tuple
    qkv: list         # the specs of q (by rows), k and v (whole)
    rows: object      # ``block_q`` query rows of a (B, T, H·D) operand
    whole: object     # all ``t_kv`` rows of one
    padded_kv: int
    kv_pads: list     # where K/V do not fill the score tile's lanes,
    #                   the [padded_kv, 128] VMEM buffers they are padded into


def _launch(batch, t_q, t_kv, width, block_q, dtype, packed):
    """``packed``: q, k and v are one (B, T, 3·H·D) array, columns
    ``[q | k | v][H·D]``; each is read at its own column blocks of it,
    a third of its width (``width`` = H·D) apart."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    head_blocks = width // _LANES

    def rows(part=0):
        at = part * head_blocks
        return pl.BlockSpec((1, block_q, _LANES),
                            lambda b, j, i: (b, i, at + j))

    def whole(part=0):
        at = part * head_blocks
        return pl.BlockSpec((1, t_kv, _LANES), lambda b, j, i: (b, 0, at + j))

    padded_kv = _round_up(t_kv, _LANES)
    kv_pads = [] if padded_kv == t_kv else [
        pltpu.VMEM((padded_kv, _LANES), dtype)
    ] * 2
    qkv = [rows(0), whole(1), whole(2)] if packed else [
        rows(), whole(), whole()
    ]
    return _Launch((batch, head_blocks, t_q // block_q), qkv, rows(),
                   whole(), padded_kv, kv_pads)


def _compiler_params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=FLASH_VMEM_BYTES
    )


def _flash_fwd(q, k, v, d, causal, scale, block_q, packed=False):
    """``packed``: q, k and v are one (B, T, 3·H·D) array, read by
    column block."""
    from jax.experimental import pallas as pl

    width = q.shape[2] // (3 if packed else 1)
    launch = _launch(q.shape[0], q.shape[1], k.shape[1], width, block_q,
                     q.dtype, packed)
    out = (*q.shape[:2], width)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, scale=scale, causal=causal),
        grid=launch.grid,
        in_specs=launch.qkv,
        out_specs=[launch.rows, launch.rows],
        out_shape=[
            jax.ShapeDtypeStruct(out, q.dtype),
            jax.ShapeDtypeStruct(out, jnp.float32),
        ],
        scratch_shapes=launch.kv_pads,
        # the K/V scratch is filled at a (batch, head block)'s first
        # query block, so those run in order on one core
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=jax.default_backend() != "tpu",
        name=KERNEL_FLASH_FWD,
    )(q, k, v)


def _flash_bwd(q, k, v, o, lse, do, d, causal, scale, block_q, packed=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    launch = _launch(*o.shape[:2], k.shape[1], o.shape[2], block_q, q.dtype,
                     packed)
    if packed:
        # a batch row of the packed gradient and of its column sums
        # stays in VMEM over the head blocks, which therefore run in order
        def row(shape):
            return pl.BlockSpec((1, *shape[1:]), lambda b, j, i: (b, 0, 0))

        sums = (q.shape[0], 8, q.shape[2])
        out_specs = [row(q.shape), row(sums)]
        out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                     jax.ShapeDtypeStruct(sums, jnp.float32)]
    else:
        out_specs = [launch.rows, launch.whole, launch.whole]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in (q, k, v)]
    # a custom_vjp's backward is traced outside the forward's scope
    with jax.named_scope(SCOPE_ATTN_CORE):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, d=d, scale=scale, causal=causal,
                              packed=packed),
            grid=launch.grid,
            in_specs=launch.qkv + [launch.rows] * 3,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((launch.padded_kv, _LANES), jnp.float32)
            ] * 2 + launch.kv_pads,
            compiler_params=_compiler_params(
                "parallel", "arbitrary" if packed else "parallel",
                "arbitrary",
            ),
            interpret=jax.default_backend() != "tpu",
            name=KERNEL_FLASH_BWD,
        )(q, k, v, o, lse, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, d, causal, scale, block_q):
    """softmax(q kᵀ · scale) v per head of width ``d`` over (B, T, H·D)
    operands: Q in whole blocks of ``block_q`` rows, K/V at their own
    length. Scores, softmax and both matmuls of a [block_q, padded_kv]
    tile happen in VMEM: no [B, H, T, T] tensor reaches HBM, forward or
    backward. bf16 (input dtype) MXU operands, f32 accumulation, f32
    max/sum/exp, probabilities cast only for the matmuls that consume
    them."""
    return _flash_fwd(q, k, v, d, causal, scale, block_q)[0]


def _flash_core_fwd(q, k, v, d, causal, scale, block_q):
    o, lse = _flash_fwd(q, k, v, d, causal, scale, block_q)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(d, causal, scale, block_q, res, do):
    return tuple(_flash_bwd(*res, do, d, causal, scale, block_q))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _flash_core_packed(qkv, bias, d, causal, scale, block_q):
    """:func:`_flash_core` for self-attention from one projection:
    ``qkv`` (B, T, 3·H·D), the projection's product with columns
    ``[q | k | v][H·D]``, and its ``bias`` (3·H·D,), added here. The
    same kernels; their block specs point into the one array, the
    backward writes the one gradient of its shape, and the bias's
    gradient is that gradient's column sums, which the kernel has in
    VMEM as it stores them."""
    return _flash_core_packed_fwd(qkv, bias, d, causal, scale, block_q)[0]


def _flash_core_packed_fwd(qkv, bias, d, causal, scale, block_q):
    qkv = qkv + bias  # the projection's: fused into its product, no scope
    with jax.named_scope(SCOPE_ATTN_CORE):
        o, lse = _flash_fwd(qkv, qkv, qkv, d, causal, scale, block_q,
                            packed=True)
    return o, (qkv, o, lse)


def _flash_core_packed_bwd(d, causal, scale, block_q, res, do):
    qkv, o, lse = res
    dqkv, sums = _flash_bwd(qkv, qkv, qkv, o, lse, do, d, causal, scale,
                            block_q, packed=True)
    return dqkv, jnp.sum(sums[:, 0], axis=0).astype(qkv.dtype)


_flash_core_packed.defvjp(_flash_core_packed_fwd, _flash_core_packed_bwd)


def _flash_attention(q, k, v, causal, scale):
    """(B, T, H, D) in and out around :func:`_flash_core`: fold heads
    into lanes — (B, T, H·D), the layout the projections around the core
    read and write. K and V always go in at
    their own length. Where a block divides ``t_q`` (1,200 tokens) so
    does Q, and the reshape is all: the operands, the residuals and the
    gradients keep the input's length. Otherwise Q is padded to its
    blocks in HBM and the padding sliced off the output; padded query
    rows get zero cotangents, so the gradients are exact either way."""
    b, t_q, h, d = q.shape
    blocks = flash_block_sizes(t_q, k.shape[1], q.dtype)
    if blocks.padded_q == t_q:
        metrics.count("attn.path.flash_exact")
    else:
        metrics.count("attn.path.flash_padded")
        q = jnp.pad(q, ((0, 0), (0, blocks.padded_q - t_q), (0, 0), (0, 0)))
    o = _flash_core(
        *(x.reshape(b, x.shape[1], h * d) for x in (q, k, v)),
        d, causal, scale, blocks.block_q,
    )
    return o[:, :t_q].reshape(b, t_q, h, d)


def _flash_attention_packed(qkv, bias, num_heads, causal, scale):
    """(B, T, 3·H·D) in, (B, T, H, D) out around
    :func:`_flash_core_packed`, where a query block divides T."""
    b, t, width = qkv.shape
    d = width // (3 * num_heads)
    blocks = flash_block_sizes(t, t, qkv.dtype)
    o = _flash_core_packed(qkv, bias, d, causal, scale, blocks.block_q)
    return o.reshape(b, t, num_heads, d)


def _per_batch_shard(fn, batch, n_sharded, n_whole=0):
    """``fn`` as the program being traced may call a kernel
    (:func:`_placement`): as it is, or through
    :func:`shard_over_batch` on a declared mesh."""
    placed = _placement()
    if not isinstance(placed, tuple):
        return fn
    metrics.count("attn.path.shard_map")
    return shard_over_batch(fn, placed, batch, n_sharded, n_whole)


def shard_over_batch(fn, placed, batch, n_sharded, n_whole=0):
    """``fn`` through ``shard_map`` over a declared mesh's batch axes
    (``placed``: :func:`_placement`'s tuple), its first ``n_sharded``
    arguments and its result cut along their leading (batch) dimension
    and the ``n_whole`` after them given to every shard whole."""
    from jax.sharding import PartitionSpec as P

    from blendjax.parallel.collectives import _shard_map

    mesh, axes, n = placed
    # an explicit kernel backend the axes do not divide runs replicated
    spec = P(axes) if n > 1 and batch % n == 0 else P()
    # check=False: pallas_call's out_shape carries no varying-
    # mesh-axes annotation, which the VMA checker requires
    return _shard_map(
        fn, mesh, in_specs=(spec,) * n_sharded + (P(),) * n_whole,
        out_specs=spec, check=False,
    )


def local_attention(q, k, v, causal: bool = False, scale=None,
                    backend: str = "auto"):
    """Exact multi-head attention over (B, T, H, D) tensors. (One
    projection's packed ``qkv``: :func:`local_attention_packed`.)

    ``backend``: ``"xla"`` | ``"flash"`` | ``"auto"`` (the policy
    above). ``"flash"`` raises on an ineligible input instead of
    silently measuring xla — same explicitness contract as the tile
    decode's ``use_pallas`` — and off a TPU runs the kernel in
    interpreter mode. The path traced is counted (once per trace)
    under ``attn.path.xla`` or ``attn.path.flash``, the kernel's also
    under ``attn.path.flash_exact`` (no operand padded in HBM) or
    ``attn.path.flash_padded``, plus ``attn.path.shard_map`` when the
    kernel was wrapped for a mesh.
    """
    if backend not in ("auto", "flash", "xla"):
        # ValueError, not assert: a typo'd backend under `python -O`
        # must not silently measure the xla path
        raise ValueError(f"unknown attention backend {backend!r}")
    if backend == "flash" and not flash_supported(q, k):
        raise ValueError(
            "flash attention backend requested but unsupported here: "
            "needs (B, T, H, D) inputs whose heads fill 128 lanes whole "
            f"(D = 128, or D | 128 and H % (128 // D) == 0) and at most "
            f"{FLASH_MAX_KV} keys, got q {q.shape} kv {k.shape}"
        )
    use_flash = backend == "flash" or (
        backend == "auto" and auto_picks_flash(q, k)
    )
    # one name for attention without its projections, whichever backend
    # runs it (docs/observability.md "Device scopes"); pad, transposes
    # and slice are read with the core
    with jax.named_scope(SCOPE_ATTN_CORE):
        if not use_flash:
            metrics.count("attn.path.xla")
            return reference_attention(q, k, v, causal=causal, scale=scale)
        metrics.count("attn.path.flash")
        scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
        fn = functools.partial(_flash_attention, causal=causal, scale=scale)
        return _per_batch_shard(fn, q.shape[0], 3)(q, k, v)


def attention_reads_packed(b, t, h, d, dtype, backend="auto") -> bool:
    """Whether self-attention from one projection at this shape takes
    the kernels on the packed ``qkv`` product
    (:func:`local_attention_packed`): where ``backend`` resolves to the
    fused kernel, a query block divides ``t`` (the ``flash_exact``
    geometry: 1,200 and 768 tokens, not 197) and a batch row of the
    packed gradient, which the backward keeps in VMEM twice (the block
    and its write-back), stays within a quarter of FLASH_VMEM_BYTES
    (5.5 MB at 1,200 tokens of 768 in bf16; about 4,700 tokens at that
    width). A model asks before it computes the projection, which it
    writes as a flat product only where this holds."""
    q = jax.ShapeDtypeStruct((b, t, h, d), dtype)
    if backend == "flash":
        takes_kernel = flash_supported(q)
    else:
        takes_kernel = backend == "auto" and auto_picks_flash(q)
    row_bytes = t * 3 * h * d * jnp.dtype(dtype).itemsize
    return bool(
        takes_kernel
        and flash_block_sizes(t, t, dtype).padded_q == t
        and 2 * row_bytes <= FLASH_VMEM_BYTES // 4
    )


@jax.custom_vjp
def _flat_product(x, kernel):
    return x @ kernel


def _flat_product_fwd(x, kernel):
    return x @ kernel, (x, kernel)


def _flat_product_bwd(res, dy):
    """The kernel's gradient as ``dyᵀ x``, (3·H·D, C) row-major, which
    is the layout the (C, 3, H, D) parameter has in the fused step's
    carry (C minor: 64 as the minor dimension would pad), computed from
    row-major ``x`` and ``dy`` as they are, and held there by the
    barrier. Left to itself XLA folds the parameter's reshape into the
    product, and that five-dimensional product with 64 output lanes
    wants ``dy`` token-minor: one ``copy`` of the packed gradient a
    layer (compiled for a v5e, PR 35); written the other way round it
    transposes the f32 result twice in the optimizer instead."""
    x, kernel = res
    dx = lax.dot_general(dy, kernel, (((2,), (1,)), ((), ())))
    dkernel = lax.dot_general(dy, x, (((0, 1), (0, 1)), ((), ())))
    return dx, lax.optimization_barrier(dkernel).T


_flat_product.defvjp(_flat_product_fwd, _flat_product_bwd)


def packed_qkv_projection(x, kernel, dtype):
    """``x`` (B, T, C) times a ``DenseGeneral((3, H, D))`` kernel
    (C, 3, H, D) as one flat product: (B, T, 3·H·D), columns
    ``[q | k | v][H][D]``, row-major as the product writes it — the
    operand of :func:`local_attention_packed`, with nothing between the
    two. (``DenseGeneral``'s own five-dimensional result XLA lays out
    token-minor, and each slice of it is copied into the row-major
    layout a custom call takes.) No bias: the packed entry adds it."""
    c = kernel.shape[0]
    return _flat_product(
        x.astype(dtype), kernel.reshape(c, -1).astype(dtype)
    )


def local_attention_packed(qkv, num_heads, bias=None, causal: bool = False,
                           scale=None, backend: str = "auto"):
    """:func:`local_attention` for self-attention from one projection:
    ``qkv`` (B, T, 3·H·D), columns ``[q | k | v][H][D]``
    (:func:`packed_qkv_projection`), and optionally the projection's
    ``bias`` (3·H·D,), still to be added. Returns (B, T, H, D).

    Where :func:`attention_reads_packed` holds the fused kernels read
    q, k and v out of the one array by column block and the backward
    writes one gradient of its shape (and the bias's, its column sums),
    so no operation of activation size stands between the projection,
    or its transposes, and the kernels; counted under
    ``attn.path.flash_packed`` beside ``flash`` and ``flash_exact``.
    Elsewhere the array is sliced and :func:`local_attention` takes the
    three tensors: the same numbers either way."""
    b, t, width = qkv.shape
    d = width // (3 * num_heads)
    if bias is None:
        bias = jnp.zeros((width,), qkv.dtype)
    if not attention_reads_packed(b, t, num_heads, d, qkv.dtype, backend):
        q, k, v = (x.reshape(b, t, num_heads, d)
                   for x in jnp.split(qkv + bias, 3, axis=2))
        return local_attention(q, k, v, causal, scale, backend)
    metrics.count("attn.path.flash")
    metrics.count("attn.path.flash_exact")
    metrics.count("attn.path.flash_packed")
    fn = functools.partial(
        _flash_attention_packed, num_heads=num_heads, causal=causal,
        scale=float(scale if scale is not None else d ** -0.5),
    )
    return _per_batch_shard(fn, b, 1, 1)(qkv, bias)
