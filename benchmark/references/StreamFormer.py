"""The ViT encoder as StreamFormer runs it, plain (Dosovitskiy et al.,
arXiv:2010.11929, eq. 1-4, pre-LN): patch embedding (a stride-``patch``
convolution written as a matrix product over flattened patches), learned
position table, ``depth`` blocks of LN -> multi-head self-attention ->
residual, LN -> MLP(4x, GELU) -> residual, final LN. float32 throughout,
attention materialised, no kernel. Departures from the paper, as the
configuration file lists them: no class token (the tokens are
mean-pooled after the final LN), a regression head, LN eps 1e-6, tanh
GELU. Parameters are read from the flax tree by name; independent of
``blendjax.models.transformer``."""

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def forward(params, images, *, patch, num_heads, **_):
    x = images.astype(jnp.float32) / 255.0
    b, h, w, c = x.shape
    gh, gw = h // patch, w // patch
    x = x.reshape(b, gh, patch, gw, patch, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, gh * gw, patch * patch * c)
    pe = params["patch_embed"]
    x = x @ pe["kernel"].reshape(patch * patch * c, -1) + pe["bias"]
    x = x + params["pos_embed"]
    depth = sum(1 for k in params if k.startswith("block"))
    for i in range(depth):
        p = params[f"block{i}"]
        att = p["MultiHeadAttention_0"]
        y = _layer_norm(x, p["LayerNorm_0"])
        qkv = jnp.einsum("btc,cjhd->btjhd", y, att["qkv"]["kernel"])
        qkv = qkv + att["qkv"]["bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        o = o.reshape(b, gh * gw, num_heads * d)
        x = x + o @ att["proj"]["kernel"] + att["proj"]["bias"]
        y = _layer_norm(x, p["LayerNorm_1"])
        y = jax.nn.gelu(
            y @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"],
            approximate=True,
        )
        x = x + y @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]
    x = _layer_norm(x, params["LayerNorm_0"]).mean(axis=1)
    return x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
