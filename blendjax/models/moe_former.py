"""StreamMoEFormer: a decoder stack of attention and feed-forward
sub-blocks with sandwich norms, sliding-window and full attention by
layer, leading dense layers and then routed experts, over the stream's
patch tokens.

The layer layout of the sparse-expert transformers of 2025 that mix
windowed and full attention (``afmoe``: ``layer_types``,
``num_dense_layers``): every layer is

    h <- h + RMSNorm(attn(RMSNorm(h)))
    h <- h + RMSNorm(ffn(RMSNorm(h)))

with its own four norms. ``attn`` is grouped-query causal attention
(:class:`blendjax.models.transformer.MultiHeadAttention`) with a per-head
RMSNorm on q and k, an output gate ``sigmoid(x W_gate)`` and no bias; a
sliding layer (``S`` in ``layer_types``) sees the latest ``window`` keys
and rotates q and k (RoPE, ``rope_theta``), a full layer (``F``) sees
every earlier key and has no position input. ``ffn`` is a dense SwiGLU
(``dense_width``) in the first ``num_dense_layers`` layers and routed
gated experts with a shared one after
(:class:`blendjax.models.moe.RoutedExperts` with ``gated``, this chip's
share of the experts).

Around the stack the stream side is :class:`StreamFormer`'s
:class:`PatchEmbed`, scaled by ``sqrt(dim)`` (the muP input scale) and
with no position table, and after it a final norm, mean pooling and a
float32 regression head. Float32 parameters, the sub-blocks' products in
the precision policy's dtype; the patch embedding, the residual stream
and what the routers read, every norm's statistics, the rotary embedding
and the output gate's sigmoid in float32.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from blendjax.models.moe import RoutedExperts, SwiGLU
from blendjax.models.transformer import MultiHeadAttention, PatchEmbed, RMSNorm
from blendjax.precision import default_compute_dtype
from blendjax.utils.metrics import (
    DECODER_RESIDUALS,
    SAVED_RESIDUALS,
    SCOPE_ATTN_BLOCK,
)


class DecoderLayer(nn.Module):
    """One layer: the attention and the feed-forward sub-block, each
    between its pre-norm and its post-norm, each added to ``h``."""

    attn: nn.Module
    ffn: nn.Module
    eps: float = 1e-5
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        def norm(name, dtype=self.dtype):
            return RMSNorm(self.eps, dtype=dtype, name=name)

        y = norm("attn_norm")(x)
        with jax.named_scope(SCOPE_ATTN_BLOCK):
            y = self.attn(y)
        x = x + norm("attn_post_norm", x.dtype)(y)
        # the feed-forward part's products cast its input to the compute
        # dtype; the router reads it as it comes
        y = self.ffn(norm("ffn_norm", x.dtype)(x))
        return x + norm("ffn_post_norm", x.dtype)(y)


class StreamMoEFormer(nn.Module):
    """Patchify -> x sqrt(dim) -> layers by ``layer_types`` -> final norm
    -> mean pool -> head. Layers are named ``layer{i}`` whatever
    ``remat`` says; ``layer{i}/attn`` and ``layer{i}/ffn`` hold the
    sub-blocks."""

    patch: int = 8
    dim: int = 256
    layer_types: str = "SSSF"  # S: sliding window, F: full causal
    num_dense_layers: int = 1
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 64
    window: int = 2048
    rope_theta: float = 10000.0
    dense_width: int = 512
    num_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 128
    shared_width: int = 128
    routed_scaling: float = 1.0
    experts_held: int | None = None
    expert_offset: int = 0
    norm_eps: float = 1e-5
    num_outputs: int = 16
    attn_backend: str = "auto"
    # recompute each layer in the backward pass but for its large
    # products' and the attention kernel's outputs
    # (utils.metrics.SAVED_RESIDUALS and DECODER_RESIDUALS), which are kept
    remat: bool = False
    dtype: Any = None  # None -> the precision policy's compute dtype

    @nn.compact
    def __call__(self, images):
        dtype = default_compute_dtype(self.dtype)
        if not self.layer_types or set(self.layer_types) - set("SF"):
            raise ValueError(
                f"layer_types {self.layer_types!r}: letters are S and F"
            )
        # The residual stream is float32 from a float32 embedding on: x
        # sqrt(dim) makes it large against the unit-scale post-normed
        # outputs added to it, which a bf16 stream would keep few bits of,
        # and the routers would read the embedding's bf16 rounding, shared
        # by the many tokens of alike patches, which then tip a near tie
        # of expert scores together
        with jax.default_matmul_precision("highest"):
            x = PatchEmbed(
                self.dim, self.patch, dtype=jnp.float32, name="patch_embed"
            )(images)
        b, hh, ww, c = x.shape
        x = x.reshape(b, hh * ww, c) * math.sqrt(c)
        layer_cls = nn.remat(
            DecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(
                *SAVED_RESIDUALS, *DECODER_RESIDUALS
            ),
        ) if self.remat else DecoderLayer
        for i, kind in enumerate(self.layer_types):
            sliding = kind == "S"
            # unbound (``parent=None``): each is adopted by its layer, as
            # ``layer{i}/attn`` and ``layer{i}/ffn``
            attn = MultiHeadAttention(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, use_bias=False, causal=True,
                qk_norm=True, output_gate=True, norm_eps=self.norm_eps,
                rope_theta=self.rope_theta if sliding else None,
                window=self.window if sliding else None,
                attn_backend=self.attn_backend, dtype=dtype, parent=None,
            )
            if i < self.num_dense_layers:
                ffn = SwiGLU(self.dense_width, dtype=dtype, parent=None)
            else:
                ffn = RoutedExperts(
                    num_experts=self.num_experts,
                    experts_per_token=self.experts_per_token,
                    expert_width=self.expert_width,
                    shared_width=self.shared_width,
                    experts_held=self.experts_held,
                    expert_offset=self.expert_offset,
                    scaling=self.routed_scaling, gated=True, dtype=dtype,
                    parent=None,
                )
            x = layer_cls(
                attn, ffn, eps=self.norm_eps, dtype=dtype, name=f"layer{i}"
            )(x)
        x = RMSNorm(self.norm_eps, dtype=jnp.float32, name="norm_f")(x)
        x = x.mean(axis=1)
        return nn.Dense(self.num_outputs, dtype=jnp.float32,
                        param_dtype=jnp.float32, name="head")(x)
