"""CubeRegressor's forward pass, plain: four 3x3 stride-2 SAME
convolutions with GELU (tanh form, flax's default), global average pool,
Dense(256)+GELU, Dense(16). float32 throughout, parameters read from
the flax tree by name. Independent of ``blendjax.models.cnn``."""

import jax
import jax.numpy as jnp


def forward(params, images, **_):
    x = images.astype(jnp.float32) / 255.0
    n_conv = sum(1 for k in params if k.startswith("Conv_"))
    for i in range(n_conv):
        p = params[f"Conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, p["kernel"], window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p["bias"]
        x = jax.nn.gelu(x, approximate=True)
    x = x.mean(axis=(1, 2))
    x = jax.nn.gelu(
        x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"],
        approximate=True,
    )
    return x @ params["Dense_1"]["kernel"] + params["Dense_1"]["bias"]
