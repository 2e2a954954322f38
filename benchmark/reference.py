"""The comparison that decides ``correct``: the production path against a
plain reference on the first chunk group of a seeded recording.

Production is what the cell dispatches: packed tiles -> fused decode +
step, ``lax.scan`` over the group, donated state, the configuration's
precision (bf16 compute, float32 parameters), the Pallas decode kernel.
The reference shares none of that: every recorded message is decoded on
the host with ``decode_tile_delta_np``, the model is the plain float32
forward in ``references/<model>.py``, and each optimizer update is an
un-scanned, un-donated ``jax.jit`` of loss and gradient followed by the
optax update, under ``jax.default_matmul_precision("highest")`` (on a
TPU a float32 product otherwise runs in bf16 passes). A batch too large
for float32 activations is split into equal micro-batches whose
gradients are averaged, which is the same mean.

Both start from the same seeded parameters and see the same frames, so
their per-update losses differ only by the precision of the arithmetic
and, from the second update on, by what that did to the parameters.
"""

from __future__ import annotations

import numpy as np

# Largest |production - reference| / |reference| over the compared
# per-update losses.
#
# The bar a run is held to is the configuration's own
# (``reference_check.rtol`` in its file: about four times the worst
# reading on the v5e over the seeds tried, PERF.md Findings PR 22),
# because how far the two paths drift depends on how many updates are
# compared and on how much the loss already depends on the model: at
# the seeded initialisation the outputs are near zero and the loss is
# almost the labels' own second moment, so bf16 against float32 differs
# by at most 2.2e-5 over vit_b16's first 2 updates and by 2.8e-3 over
# cube_cnn's first 16, by which time its loss has fallen from 0.28 to
# 0.05. No configuration may state more than the ceiling below for the
# precision it names.
#
# bf16: activations and matrix products carry 8 bits of mantissa (3.9e-3
# a rounding), averaged over >= 128 squared errors; Adam's first steps
# are lr * sign(g), so a gradient's rounding moves the next loss only
# where it flips the sign of a small component. 8e-3 is the measured
# cube_cnn drift over a whole chunk group x 3. A path in a lower
# precision than stated (fp8 e4m3: 3 bits, 6e-2 a rounding; int8) flips
# an order of magnitude more signs and misses a bar set 4x over the
# bf16 reading; so does a missing term of the model.
# f32: a few ulps of reduction order, as blendjax.testing.equivalence.
LOSS_RTOL = {"bf16": 8e-3, "f32": 2e-5}


def decode_recording(path: str, messages: int):
    """The first ``messages`` messages of a ``.bjr`` as host arrays:
    ``[(frames uint8 (B, H, W, C), xy float32 (B, 8, 2)), ...]``."""
    from blendjax.data.replay import ReplayStream
    from blendjax.ops import tiles as T

    out = []
    refs: dict = {}
    stream = ReplayStream(path)
    try:
        for i, msg in enumerate(stream):
            if i == messages:
                break
            btid = msg.get("btid")
            T.pop_stream_refs(msg, refs, btid)
            (name, geom), = T.pop_tile_batches(msg)
            tiles = T.pop_tile_payload(
                msg, name, geom, T.expand_palette_tiles_np
            )
            frames = T.decode_tile_delta_np(
                np.asarray(refs[(name, btid)]),
                np.asarray(msg[name + T.TILEIDX_SUFFIX]), np.asarray(tiles),
            )
            out.append((frames, np.asarray(msg["xy"], np.float32)))
    finally:
        stream.close()
    if len(out) != messages:
        raise RuntimeError(f"recording holds {len(out)} of {messages} messages")
    return out


def corner_mse(pred, xy, hw):
    """Mean squared error of the 8 predicted corners in image
    coordinates normalised to [0, 1] by (width, height)."""
    import jax.numpy as jnp

    h, w = hw
    scale = jnp.asarray([w, h], jnp.float32)
    return jnp.mean((pred.reshape(-1, 8, 2) / scale - xy / scale) ** 2)


REFERENCE_LOSSES = {"corner_mse": corner_mse}


def reference_losses(forward, forward_kwargs: dict, loss: str, tx, params,
                     batches, microbatch: int) -> np.ndarray:
    """One float32 loss per update over ``batches`` from ``params``."""
    import jax
    import optax

    loss_of = REFERENCE_LOSSES[loss]

    def loss_fn(p, images, xy):
        return loss_of(
            forward(p, images, **forward_kwargs), xy, images.shape[1:3]
        )

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jax.numpy.add, a, b))

    @jax.jit
    def update(p, opt_state, grad_sum, parts):
        grads = jax.tree_util.tree_map(lambda g: g / parts, grad_sum)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    losses = []
    with jax.default_matmul_precision("highest"):
        opt_state = tx.init(params)
        for images, xy in batches:
            if len(images) % microbatch:
                raise ValueError(
                    f"microbatch {microbatch} does not divide {len(images)}"
                )
            parts = len(images) // microbatch
            total, grad_sum = 0.0, None
            for j in range(parts):
                sl = slice(j * microbatch, (j + 1) * microbatch)
                value, grads = grad_fn(params, images[sl], xy[sl])
                total += float(value)
                grad_sum = grads if grad_sum is None else add(grad_sum, grads)
            params, opt_state = update(params, opt_state, grad_sum, parts)
            losses.append(total / parts)
    return np.asarray(losses, np.float32)


def compare(production, reference, precision: str, rtol=None) -> dict:
    """``ok`` when every compared per-update loss agrees within
    ``rtol`` (the configuration's bar), which may not be looser than
    the ceiling of ``precision``."""
    if precision not in LOSS_RTOL:
        raise KeyError(f"no tolerance on record for precision {precision!r}")
    rtol = min(float(rtol or LOSS_RTOL[precision]), LOSS_RTOL[precision])
    production = np.asarray(production, np.float64)[: len(reference)]
    reference = np.asarray(reference, np.float64)
    rel = np.abs(production - reference) / np.maximum(np.abs(reference), 1e-12)
    return {
        "ok": bool(
            len(production) == len(reference) and len(reference) > 0
            and np.isfinite(rel).all() and rel.max() <= rtol
        ),
        "updates": int(len(reference)),
        "max_rel_diff": float(rel.max()) if len(rel) else None,
        "rtol": rtol,
        "production": [float(v) for v in production],
        "reference": [float(v) for v in reference],
    }
