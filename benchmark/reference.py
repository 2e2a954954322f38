"""The comparison that decides ``correct``: the production path against a
plain reference on the first chunk group of a seeded recording.

Production is what the cell dispatches: packed tiles -> fused decode +
step, ``lax.scan`` over the group, donated state, the configuration's
precision (bf16 compute, float32 parameters), the Pallas decode kernel.
The reference shares none of that: every recorded message is decoded on
the host with ``decode_tile_delta_np``, the model is the plain float32
forward in ``references/<model>.py``, the loss its plain form in
``losses/<loss>.py``, and each optimizer update is an un-scanned
``jax.jit`` of loss and gradient followed by the optax update, under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product
otherwise runs in bf16 passes). A batch too large
for float32 activations is split into equal micro-batches whose
gradients are averaged, which is the same mean.

Both start from the same seeded parameters and see the same frames, so
their per-update losses differ only by the precision of the arithmetic
and, from the second update on, by what that did to the parameters.

The reference runs first and alone: ``run.py`` makes the seeded state,
takes its parameters' :func:`parameter_checksum`, drops its moments, lets
:func:`reference_losses` consume the parameters, and only then makes the
production state with the same program from the same key, whose
parameters must give the same checksum. So a configuration of P
parameters costs 16 P bytes in its step and at most 20 P in its check,
never both at once.
"""

from __future__ import annotations

import functools

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


@functools.lru_cache(maxsize=None)
def _checksum_program():
    import jax
    import jax.numpy as jnp

    def one(x):
        bits = jax.lax.bitcast_convert_type(
            x.astype(jnp.float32), jnp.uint32
        ).reshape(-1)
        weight = 2 * jax.lax.iota(jnp.uint32, bits.size) + 1
        return jnp.sum(bits * weight, dtype=jnp.uint32)

    return jax.jit(lambda leaves: jnp.stack([one(x) for x in leaves]))


def parameter_checksum(params) -> np.ndarray:
    """One uint32 a leaf: the sum of the elements' bit patterns, each
    times an odd weight from its position, modulo 2**32. Integer sums
    are exact in any order, so one chip, a mesh and the CPU give the same
    number for the same bits, and nothing larger than a leaf is made."""
    import jax

    return np.asarray(_checksum_program()(jax.tree_util.tree_leaves(params)))


def live_bytes() -> int:
    """Bytes of every array this process holds on its devices."""
    import jax

    return sum(a.nbytes for a in jax.live_arrays())


def loss_and_grad(forward, forward_kwargs: dict, loss_of):
    """The reference's one large program, jitted: ``(parameters, images,
    labels) -> (loss, gradient)`` over one micro-batch."""
    import jax

    def loss_fn(p, images, xy):
        return loss_of(
            forward(p, images, **forward_kwargs), xy, images.shape[1:3]
        )

    return jax.jit(jax.value_and_grad(loss_fn))


def reference_losses(forward, forward_kwargs: dict, loss_of, tx, params,
                     batches, microbatch: int, watch=None) -> np.ndarray:
    """One float32 loss per update over ``batches`` from ``params``,
    which are consumed: ``update`` donates them and the moments (the
    gradient sum has no result to become and is dropped after it), and
    the accumulation donates its accumulator, so at its peak (a
    micro-batch's gradient beside the sum) the stage holds parameters 4
    + moments 8 + sum 4 + gradient 4 = 20 bytes a parameter and the
    activations of one micro-batch inside a jit.
    ``loss_of(prediction, labels, (height, width))`` is the loss's plain
    float32 form (``losses/<loss>.py`` ``reference_loss``); ``watch()``
    is called at that peak."""
    import jax
    import optax

    grad_fn = loss_and_grad(forward, forward_kwargs, loss_of)
    add = jax.jit(
        lambda a, b: jax.tree_util.tree_map(jax.numpy.add, a, b),
        donate_argnums=0,
    )

    def update(p, opt_state, grad_sum, parts):
        grads = jax.tree_util.tree_map(lambda g: g / parts, grad_sum)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    update = jax.jit(update, donate_argnums=(0, 1))
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
                if watch is not None:
                    watch()
                grad_sum = grads if grad_sum is None else add(grad_sum, grads)
                del grads
            params, opt_state = update(params, opt_state, grad_sum, parts)
            del grad_sum
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
