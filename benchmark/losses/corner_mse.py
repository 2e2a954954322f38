"""Corner regression: MSE over the 8 projected cube corners, in image
coordinates normalised to [0, 1] (``blendjax.train.corner_loss``). The
model's 16 outputs are read as (8, 2) whatever their shape, so the CNN
and the transformer train on the same stream with the same loss."""

from blendjax.train import corner_loss


def loss_fn(state, params, batch):
    pred = state.apply_fn({"params": params}, batch["image"])
    return corner_loss(
        pred.reshape(-1, 8, 2), batch["xy"],
        image_shape=batch["image"].shape[1:3],
    )


def reference_loss(pred, xy, hw):
    """The same loss, plain and in float32, for the reference: mean
    squared error of the 8 predicted corners in image coordinates
    normalised to [0, 1] by (width, height). Takes nothing of the
    program."""
    import jax.numpy as jnp

    h, w = hw
    scale = jnp.asarray([w, h], jnp.float32)
    return jnp.mean((pred.reshape(-1, 8, 2) / scale - xy / scale) ** 2)
