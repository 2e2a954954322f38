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
  block, so nothing is transposed. Any T, at its own length: K/V are
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


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *kv_pads, d, scale,
                causal):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)

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
    dq_ref[0] = _merge(dqs, heads).astype(dq_ref.dtype)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        t_kv = dk_ref.shape[1]
        dk_ref[0] = dk_acc[:t_kv].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:t_kv].astype(dv_ref.dtype)


def _specs(q, k, block_q):
    """Block specs, grid and the K/V scratch of one launch: Q-shaped
    operands by ``block_q`` rows, K/V-shaped ones whole, and, where K/V
    do not fill the score tile's lanes, the [padded_kv, 128] VMEM
    buffers the kernel pads them into."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, j))
    whole = pl.BlockSpec(
        (1, k.shape[1], _LANES), lambda b, j, i: (b, 0, j)
    )
    grid = (q.shape[0], q.shape[2] // _LANES, q.shape[1] // block_q)
    padded_kv = _round_up(k.shape[1], _LANES)
    kv_pads = [] if padded_kv == k.shape[1] else [
        pltpu.VMEM((padded_kv, _LANES), k.dtype)
    ] * 2
    return rows, whole, grid, padded_kv, kv_pads


def _compiler_params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=FLASH_VMEM_BYTES
    )


def _flash_fwd(q, k, v, d, causal, scale, block_q):
    from jax.experimental import pallas as pl

    rows, whole, grid, _, kv_pads = _specs(q, k, block_q)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, scale=scale, causal=causal),
        grid=grid,
        in_specs=[rows, whole, whole],
        out_specs=[rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
        ],
        scratch_shapes=kv_pads,
        # the K/V scratch is filled at a (batch, head block)'s first
        # query block, so those run in order on one core
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=jax.default_backend() != "tpu",
        name=KERNEL_FLASH_FWD,
    )(q, k, v)


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
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, o, lse = res
    rows, whole, grid, padded_kv, kv_pads = _specs(q, k, block_q)
    # a custom_vjp's backward is traced outside the forward's scope
    with jax.named_scope(SCOPE_ATTN_CORE):
        return tuple(pl.pallas_call(
            functools.partial(_bwd_kernel, d=d, scale=scale, causal=causal),
            grid=grid,
            in_specs=[rows, whole, whole, rows, rows, rows],
            out_specs=[rows, whole, whole],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v)],
            scratch_shapes=[
                pltpu.VMEM((padded_kv, _LANES), jnp.float32)
            ] * 2 + kv_pads,
            compiler_params=_compiler_params(
                "parallel", "parallel", "arbitrary"
            ),
            interpret=jax.default_backend() != "tpu",
            name=KERNEL_FLASH_BWD,
        )(q, k, v, o, lse, do))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_attention(q, k, v, causal, scale):
    """(B, T, H, D) in and out around :func:`_flash_core`: fold heads
    into lanes — (B, T, H·D), the layout the projections around the core
    read and write, so nothing is transposed. K and V always go in at
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


def local_attention(q, k, v, causal: bool = False, scale=None,
                    backend: str = "auto"):
    """Exact multi-head attention over (B, T, H, D) tensors.

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
        placed = _placement()
        if isinstance(placed, tuple):
            from jax.sharding import PartitionSpec as P

            from blendjax.parallel.collectives import _shard_map

            mesh, axes, n = placed
            # an explicit "flash" the axes do not divide runs replicated
            spec = P(axes) if n > 1 and q.shape[0] % n == 0 else P()
            # check=False: pallas_call's out_shape carries no varying-
            # mesh-axes annotation, which the VMA checker requires
            fn = _shard_map(fn, mesh, in_specs=(spec,) * 3, out_specs=spec,
                            check=False)
            metrics.count("attn.path.shard_map")
        return fn(q, k, v)
