"""Image preprocessing ops: uint8 -> normalized compute dtype (+ optional
gamma) and flip augmentation, all plain jnp that XLA fuses into the
consuming op."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gamma_correct(x, gamma: float = 2.2):
    """float image in [0,1] -> gamma-corrected (reference does this on CPU
    numpy, ``offscreen.py:105-112``)."""
    return jnp.power(jnp.clip(x, 0.0, 1.0), 1.0 / gamma)


def normalize_uint8(x, dtype=jnp.bfloat16):
    """uint8 -> [0,1] in compute dtype (fuses into the next matmul/conv)."""
    return x.astype(dtype) / jnp.asarray(255.0, dtype)


def maybe_normalize_uint8(x, dtype=jnp.bfloat16):
    """Model-input canonicalization: uint8 is scaled to [0,1]; float input
    is assumed already normalized and only cast. The single shared guard
    all blendjax models use, so the semantics can't drift per-model."""
    if x.dtype == jnp.uint8:
        return normalize_uint8(x, dtype)
    return x.astype(dtype)


def _flip_bits(rng, b: int):
    """Per-sample flip decisions — the ONE bit-draw scheme shared by the
    paired (`augment.random_flip_with_points`) and unpaired flips; they
    must stay key-compatible (recorded augmentation sequences depend on
    flipping the same samples for the same key)."""
    return jax.random.bernoulli(rng, 0.5, (b,))


def random_flip(rng, x, axis: int = 2):
    """Batched random horizontal flip (augmentation; per-sample bit)."""
    b = x.shape[0]
    bits = _flip_bits(rng, b)
    flipped = jnp.flip(x, axis=axis)
    shape = (b,) + (1,) * (x.ndim - 1)
    return jnp.where(bits.reshape(shape), flipped, x)


def uint8_gamma_normalize(x, gamma: float = 2.2, dtype=jnp.float32):
    """uint8 NHWC -> gamma-corrected [0,1] image in ``dtype`` (plain jnp;
    XLA fuses it into the consuming op)."""
    return gamma_correct(normalize_uint8(x, jnp.float32), gamma).astype(dtype)


def embed_patches(images, kernel, bias, dtype=jnp.bfloat16):
    """Patch embedding as one matrix product: ``(B, H, W, C)`` frames
    are cut into ``p`` x ``p`` patches, each flattened row-major over
    ``(p, p, C)``, and multiplied by ``kernel`` ``(p, p, C, D)`` viewed
    as ``(p*p*C, D)``; plus ``bias`` -> ``(B, H/p, W/p, D)`` in ``dtype``
    (inputs in ``dtype``, float32 accumulation on the MXU). The same
    numbers as the ``p`` x ``p`` stride-``p`` convolution with that
    kernel. uint8 frames are scaled to [0, 1] as everywhere
    (:func:`maybe_normalize_uint8`); any ``p`` that divides ``H`` and
    ``W`` and any ``C``.

    Why a product (v5e, 8 frames of 480x640x4 u8, p 16, D 768; PERF.md
    PR 34): XLA ran the convolution's 4 input channels at 19 GB/s and
    5 % of the MXU, 2.87 ms an update with the batch-minor copy of the
    frames it asked for; this form takes 0.75. The transposition is
    written on the u8 frames, but XLA moves the scaling in front of it,
    fuses it with the scan's slice and re-lays the bf16 frames out once
    before the product (``scripts/patch_embed_time.py`` has the other
    forms and their times)."""
    p, _, c, dim = kernel.shape
    b, h, w, _ = images.shape
    gh, gw = h // p, w // p
    x = images.reshape(b, gh, p, gw, p, c).transpose(0, 1, 3, 2, 4, 5)
    x = maybe_normalize_uint8(x.reshape(b, gh, gw, p * p * c), dtype)
    return x @ kernel.astype(dtype).reshape(p * p * c, dim) + bias.astype(dtype)
