"""StreamHybrid: a stack whose layers follow a pattern string, one mixer
a layer, over the stream's patch tokens.

The layer layout of the hybrid state-space / sparse-expert / attention
language models of 2025 (``nemotron_h``: ``hybrid_override_pattern``):
every layer is ``h <- h + mixer(RMSNorm(h))`` with ONE mixer, chosen by
the layer's letter:

- ``M``: a Mamba-2 mixer (:class:`Mamba2Mixer`; Dao & Gu,
  arXiv:2405.21060): input projection, a causal depthwise convolution
  over the latest ``conv_kernel`` tokens, the selective state-space scan
  (:func:`blendjax.ops.ssd.ssd_chunked`), a gated grouped RMSNorm and the
  output projection;
- ``E``: an expert layer (:class:`blendjax.models.moe.RoutedExperts`):
  sigmoid scores, top-k without drops, ungated ReLU^2 experts and a
  shared expert, this chip's share of the experts;
- ``*``: grouped-query causal self-attention
  (:class:`blendjax.models.transformer.MultiHeadAttention` with
  ``num_kv_heads``), no rotary embedding.

No projection has a bias; the convolution has one. Around the stack the
stream side is :class:`StreamFormer`'s: :class:`PatchEmbed`, a learned
position table, mean pooling after the final norm and a float32
regression head, so the model trains on the same stream with the same
loss. Float32 parameters, compute in the precision policy's dtype; the
router, the softplus, the scan's decays and every norm's statistics in
float32.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from blendjax.models.moe import RoutedExperts
from blendjax.models.transformer import MultiHeadAttention, PatchEmbed
from blendjax.ops.ssd import ssd_chunked
from blendjax.precision import default_compute_dtype
from blendjax.utils.metrics import (
    RESIDUAL_IN_PROJ,
    SAVED_RESIDUALS,
    SCOPE_SSM_MIXER,
    saved_residual,
)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale`` with float32 statistics;
    ``groups`` > 1 takes the mean square over each of that many equal
    groups of channels (one ``scale`` over all of them)."""

    eps: float = 1e-5
    groups: int = 1
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        dtype = default_compute_dtype(self.dtype)
        scale = self.param(
            "scale", nn.initializers.ones_init(), (x.shape[-1],), jnp.float32
        )
        y = x.astype(jnp.float32)
        grouped = y.reshape(*y.shape[:-1], self.groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + self.eps
        )
        return (grouped.reshape(y.shape) * scale).astype(dtype)


def _dt_bias_init(dt_min: float, dt_max: float, floor: float):
    """The published Mamba-2 init: the inverse softplus of a step drawn
    log-uniformly from ``[dt_min, dt_max]`` and floored."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(dt_min), math.log(dt_max)
        ))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def causal_depthwise_conv(x, kernel, bias):
    """``y_t = bias + sum_k kernel[k] * x_{t - (K - 1) + k}`` a channel
    over (B, T, C), zeros before the first token; float32 sums."""
    taps = kernel.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for k in range(taps):
        y = y + kernel[k].astype(jnp.float32) * padded[:, k:k + t].astype(
            jnp.float32
        )
    return y


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer, ``(B, T, C) -> (B, T, C)``: ``num_heads`` heads
    of ``head_dim`` over a state of ``state_size``, ``B`` and ``C`` shared
    by the heads of each of ``n_groups`` groups."""

    num_heads: int
    head_dim: int
    state_size: int
    n_groups: int
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        dtype = default_compute_dtype(self.dtype)
        b, t, c = x.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, bc = h * p, 2 * g * n
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (self.conv_kernel, inner + bc), jnp.float32,
        )
        conv_bias = self.param(
            "conv_bias", nn.initializers.zeros_init(), (inner + bc,),
            jnp.float32,
        )
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(self.dt_min, self.dt_max, self.dt_floor),
            (h,), jnp.float32,
        )
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        d = self.param("D", nn.initializers.ones_init(), (h,), jnp.float32)
        with jax.named_scope(SCOPE_SSM_MIXER):
            zxbcdt = saved_residual(nn.Dense(
                2 * inner + bc + h, use_bias=False, dtype=dtype,
                param_dtype=jnp.float32, name="in_proj",
            )(x), RESIDUAL_IN_PROJ)
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + bc], axis=-1)
            # the convolution is a channel's own: x, B and C go through
            # it apart, so that each is written once, as the array the
            # scan reads (one convolution over all three, split
            # afterwards, leaves a slice of its output in HBM in front
            # of the scan's kernel)
            edges = [inner, inner + g * n]
            xs, b_in, c_in = (
                nn.silu(causal_depthwise_conv(part, kernel, bias)).astype(dtype)
                for part, kernel, bias in zip(
                    jnp.split(xbc, edges, axis=-1),
                    jnp.split(conv_kernel, edges, axis=-1),
                    jnp.split(conv_bias, edges),
                )
            )
            dt = nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y = ssd_chunked(
                xs.reshape(b, t, h, p), dt, -jnp.exp(a_log),
                b_in.reshape(b, t, g, n), c_in.reshape(b, t, g, n), d,
                chunk=self.chunk_size,
            ).reshape(b, t, inner)
            y = RMSNorm(self.eps, groups=g, dtype=dtype, name="norm")(
                y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
            )
            return nn.Dense(
                c, use_bias=False, dtype=dtype, param_dtype=jnp.float32,
                name="out_proj",
            )(y)


class HybridLayer(nn.Module):
    """``h + mixer(RMSNorm(h))``: one layer, whatever its mixer."""

    mixer: nn.Module
    eps: float = 1e-5
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        return x + self.mixer(
            RMSNorm(self.eps, dtype=self.dtype, name="norm")(x)
        )


class StreamHybrid(nn.Module):
    """Patchify -> layers by ``pattern`` -> final norm -> mean pool ->
    head. Layers are named ``layer{i}`` whatever ``remat`` says.

    ``num_experts`` routed experts are scored by every expert layer's
    router; the ``experts_held`` from ``expert_offset`` on are held here
    (all of them by default) and the layer computes their part of the
    result, which is what one chip of an expert-parallel deployment
    does: see :class:`blendjax.models.moe.RoutedExperts`.
    """

    patch: int = 16
    dim: int = 256
    pattern: str = "ME*"
    # M: the Mamba-2 mixer
    mamba_num_heads: int = 8
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # *: grouped-query causal attention
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 64
    # E: the expert layer
    num_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 256
    shared_width: int = 512
    routed_scaling: float = 1.0
    experts_held: int | None = None
    expert_offset: int = 0
    norm_eps: float = 1e-5
    num_outputs: int = 16
    attn_backend: str = "auto"
    # recompute each layer in the backward pass but for its large
    # products' outputs (utils.metrics.SAVED_RESIDUALS), which are kept
    remat: bool = False
    dtype: Any = None  # None -> the precision policy's compute dtype

    @nn.compact
    def __call__(self, images):
        dtype = default_compute_dtype(self.dtype)
        # unbound (``parent=None``): each is adopted by its layer, as
        # ``layer{i}/mixer``
        mixers = {
            "M": lambda: Mamba2Mixer(
                num_heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
                state_size=self.ssm_state_size, n_groups=self.n_groups,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                eps=self.norm_eps, dtype=dtype, parent=None,
            ),
            "E": lambda: RoutedExperts(
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                expert_width=self.expert_width,
                shared_width=self.shared_width,
                experts_held=self.experts_held,
                expert_offset=self.expert_offset,
                scaling=self.routed_scaling, dtype=dtype, parent=None,
            ),
            "*": lambda: MultiHeadAttention(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, use_bias=False, causal=True,
                attn_backend=self.attn_backend, dtype=dtype, parent=None,
            ),
        }
        unknown = set(self.pattern) - set(mixers)
        if unknown or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: letters are {sorted(mixers)}"
            )
        x = PatchEmbed(
            self.dim, self.patch, dtype=dtype, name="patch_embed"
        )(images)
        b, hh, ww, c = x.shape
        x = x.reshape(b, hh * ww, c)
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02), (1, hh * ww, c),
            jnp.float32,
        )
        x = x + pos.astype(dtype)
        layer_cls = nn.remat(
            HybridLayer,
            policy=jax.checkpoint_policies.save_only_these_names(
                *SAVED_RESIDUALS
            ),
        ) if self.remat else HybridLayer
        for i, kind in enumerate(self.pattern):
            x = layer_cls(
                mixers[kind](), eps=self.norm_eps, dtype=dtype,
                name=f"layer{i}",
            )(x)
        x = RMSNorm(self.norm_eps, dtype=jnp.float32, name="norm_f")(x)
        x = x.mean(axis=1)
        return nn.Dense(self.num_outputs, dtype=jnp.float32,
                        param_dtype=jnp.float32, name="head")(x)
