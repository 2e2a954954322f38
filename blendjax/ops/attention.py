"""Local (single-device) attention backends.

Net-new vs the reference (blendtorch has no sequence models, SURVEY.md
§2.4). Two exact backends behind one call:

- ``xla``: :func:`blendjax.parallel.ring.reference_attention` — plain
  einsum attention with bf16 MXU matmuls, f32 score accumulation, and
  f32 softmax. Materializes the (B, H, T, T) score tensor in HBM.
- ``flash``: the Pallas TPU flash-attention kernel
  (``jax.experimental.pallas.ops.tpu.flash_attention``) — streaming
  softmax in VMEM, never materializing the score tensor. fwd+bwd via
  the kernel's own custom VJP.

``auto`` policy (v5e measurements, full train steps — StreamFormer
dim 512 depth 8 heads 4):

- ISOLATED attention fwd+bwd favors flash past ~1k tokens (T=3072:
  2.43 vs 3.33 ms, 1.37x), but IN-MODEL the materialized path keeps
  winning well beyond that — T=3072: 39.4 vs 31.3 img/s; T=6144
  (1.2 GB/layer transient scores): 9.7 vs 7.8 img/s — the kernel's
  separate bwd passes cost more than XLA's fused attention backward
  while HBM still absorbs the score tensors.
- What the materialized path cannot do is run when the saved-for-
  backward score tensors stop fitting (e.g. T=16k at B=1, H=4: ~4.3
  GB/layer of f32 probs — a couple of layers exhaust a 16 GB chip).

So ``auto`` defers to ``xla`` until a single call's score residual
would exceed :data:`FLASH_RESIDUAL_BYTES`, and takes ``flash`` beyond
— flash is the long-context enabler, not a mid-length speedup, on
this hardware. Explicit ``backend="flash"`` always takes the kernel.

The sequence-parallel kernels (:mod:`blendjax.parallel.ring`,
:mod:`blendjax.parallel.ulysses`) shard T across devices *before* any
local attention runs; this module is the per-device math below them.
"""

from __future__ import annotations

import jax

from blendjax.parallel.ring import reference_attention
from blendjax.utils.metrics import SCOPE_ATTN_CORE

# Per-call score-residual budget (bytes of f32 probs saved for the
# backward pass) above which `auto` switches to the flash kernel: at
# 2 GiB/call even a handful of layers threatens a 16 GB chip, and the
# measured in-model xla advantage (see module docstring) no longer
# applies because xla can no longer run at all. (T=16k at B=1, H=4 is
# ~4.3 GB/call — comfortably over.)
FLASH_RESIDUAL_BYTES = 2 << 30
# OUR pinned block edge, not the kernel's default: every flash call
# passes an explicit ``BlockSizes`` built from this constant (see
# ``flash_block_sizes``), so ``flash_supported``'s tiling check and the
# kernel's real grid can never drift apart across jax upgrades — a new
# release changing the kernel's *default* block sizes changes nothing
# here. Sequence lengths must tile these blocks; head_dim is padded up
# to 128 but must be a multiple of 128 above it.
FLASH_BLOCK = 128


def flash_block_sizes(t_q: int, t_kv: int) -> "object":
    """Explicit kernel grid for a (t_q, t_kv) call: every forward and
    backward block edge pinned to :data:`FLASH_BLOCK` (clamped to the
    sequence lengths for short inputs). ``flash_supported`` admits a
    shape if and only if it tiles THESE blocks — one source of truth
    for eligibility and launch."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    bq = min(FLASH_BLOCK, int(t_q))
    bk = min(FLASH_BLOCK, int(t_kv))
    return BlockSizes(
        block_q=bq,
        block_k_major=bk,
        block_k=bk,
        block_b=1,
        block_q_major_dkv=bq,
        block_k_major_dkv=bk,
        block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bk,
        block_k_dq=bk,
        block_q_dq=bq,
    )


def scores_residual_bytes(q, k=None) -> int:
    """Bytes of attention probabilities one call saves for its backward
    pass — the term that makes materialized attention infeasible at
    long context. f32: ``reference_attention`` computes and normalizes
    the probs in f32 and only casts at the output matmul, so the
    saved-for-backward tensor is f32 (confirmed by the measured ~600 MB
    at B=4, H=4, T=3072 — exactly 4*4*3072^2*4 bytes)."""
    b, tq, h, _ = q.shape
    tk = q.shape[1] if k is None else k.shape[1]
    return b * h * tq * tk * 4


def flash_supported(q, k=None) -> bool:
    """Whether the Pallas TPU flash kernel can take these (B, T, H, D)
    inputs: TPU backend and sequence lengths the kernel's 128-wide
    blocks tile exactly — the KV length too, for cross-attention (the
    kernel pads head_dim up to 128; above that it requires multiples
    of 128, its own constraint)."""
    import jax

    if jax.default_backend() != "tpu":
        return False
    if not (q.ndim == 4 and q.shape[1] % FLASH_BLOCK == 0):
        return False
    d = q.shape[-1]
    if d > 128 and d % 128:
        return False
    return k is None or (
        k.ndim == 4 and k.shape[1] % FLASH_BLOCK == 0
    )


def auto_picks_flash(q, k=None) -> bool:
    """The ``auto`` policy, exposed so callers (the bench's longseq
    row) can report which backend a shape resolves to."""
    return (
        flash_supported(q, k)
        and scores_residual_bytes(q, k) > FLASH_RESIDUAL_BYTES
    )


def local_attention(q, k, v, causal: bool = False, scale=None,
                    backend: str = "auto"):
    """Exact multi-head attention over (B, T, H, D) tensors.

    ``backend``: ``"xla"`` | ``"flash"`` | ``"auto"`` (the
    memory-driven policy above). ``"flash"`` raises on an ineligible
    input instead of silently measuring xla — same explicitness
    contract as the tile decode's ``use_pallas``.
    """
    if backend not in ("auto", "flash", "xla"):
        # ValueError, not assert: a typo'd backend under `python -O`
        # must not silently measure the xla path
        raise ValueError(f"unknown attention backend {backend!r}")
    if backend == "flash" and not flash_supported(q, k):
        raise ValueError(
            "flash attention backend requested but unsupported here: "
            f"backend must be TPU and T (q {q.shape[1]}, kv "
            f"{k.shape[1]}) must be multiples of {FLASH_BLOCK}"
        )
    use_flash = backend == "flash" or (
        backend == "auto" and auto_picks_flash(q, k)
    )
    # one name for attention without its projections, whichever backend
    # runs it (docs/observability.md "Device scopes")
    if not use_flash:
        with jax.named_scope(SCOPE_ATTN_CORE):
            return reference_attention(q, k, v, causal=causal, scale=scale)

    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention,
    )

    d = q.shape[-1]
    scale = scale if scale is not None else d**-0.5
    # kernel layout is (B, H, T, D); blocks pinned explicitly so the
    # launch grid is the one flash_supported admitted, on every jax
    with jax.named_scope(SCOPE_ATTN_CORE):
        o = flash_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
            sm_scale=scale,
            block_sizes=flash_block_sizes(q.shape[1], k.shape[1]),
        )
        return o.transpose(0, 2, 1, 3)
