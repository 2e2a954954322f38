"""StreamFormer: a compact vision transformer over image streams.

Net-new (no reference counterpart — blendtorch has no sequence models,
SURVEY.md §2.4): the multi-chip showcase model. Design goals:

- **TP-friendly dims**: every Dense's output features divide by typical
  ``tensor`` axis sizes (2/4/8), so ``param_sharding_rules`` gives
  Megatron-style column sharding for free and GSPMD inserts the
  collectives.
- **SP/long-context**: with ``use_ring=True`` attention runs as
  :func:`blendjax.parallel.ring_attention` over the ``seq`` mesh axis —
  token sequences (patch tokens of large frames, or frame sequences from
  the stream) shard across devices and K/V ride the ICI ring.
- bfloat16 activations on the MXU, float32 params/softmax.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from blendjax.ops.attention import (
    attention_reads_packed,
    local_attention,
    local_attention_packed,
    packed_qkv_projection,
)
from blendjax.ops.image import embed_patches
from blendjax.parallel.ring import ring_attention
from blendjax.parallel.ulysses import ulysses_attention
from blendjax.precision import default_compute_dtype
from blendjax.utils.metrics import (
    RESIDUAL_ATTN_GATE,
    RESIDUAL_ATTN_GATED,
    RESIDUAL_ATTN_K,
    RESIDUAL_ATTN_OUT,
    RESIDUAL_ATTN_Q,
    RESIDUAL_ATTN_V,
    metrics,
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


def rotary_embedding(x, theta: float):
    """Rotate-half rotary position embedding over a head's whole width:
    ``x`` (B, T, H, D) at positions 0..T-1, channel pairs ``(i, i +
    D/2)`` turned by ``position / theta^(2i/D)``, in float32."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None]
    y = x.astype(jnp.float32)
    half = jnp.concatenate([-y[..., d // 2:], y[..., :d // 2]], axis=-1)
    return (y * jnp.cos(angles) + half * jnp.sin(angles)).astype(x.dtype)


class PackedQKV(nn.Module):
    """The ``qkv`` projection where the attention kernels read its
    product packed (:func:`blendjax.ops.attention.attention_reads_packed`):
    the parameters of the ``nn.DenseGeneral((3, H, D))`` it stands in
    for (``kernel`` ``(C, 3, H, D)`` and ``bias`` ``(3, H, D)``,
    float32, drawn the same way: the kernel in its flattened
    ``(C, 3·H·D)`` shape, so a seeded init and a saved state are
    unchanged), applied as one flat product. Returns the product
    ``(B, T, 3·H·D)`` and the flat bias, which the packed entry adds."""

    num_heads: int
    dtype: Any = None  # None -> the precision policy's compute dtype

    @nn.compact
    def __call__(self, x):
        dtype = default_compute_dtype(self.dtype)
        c, h = x.shape[-1], self.num_heads
        shape = (c, 3, h, c // h)
        kernel = self.param(
            "kernel",
            lambda key, shape, dtype: nn.initializers.lecun_normal()(
                key, (c, 3 * c), dtype
            ).reshape(shape),
            shape, jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), shape[1:], jnp.float32
        )
        return (packed_qkv_projection(x, kernel, dtype),
                bias.reshape(-1).astype(dtype))


class MultiHeadAttention(nn.Module):
    """Self-attention with its projections. By default ``num_heads`` equal
    heads of ``C // num_heads`` from one fused ``qkv`` projection, with
    biases. ``num_kv_heads`` fewer key/value heads (grouped-query
    attention: query head ``i`` reads key/value head
    ``i // (num_heads // num_kv_heads)``) and ``head_dim`` a head width
    of its own take separate ``q``, ``k``, ``v`` projections;
    ``use_bias=False`` drops every bias. On that path, all off by default:
    ``qk_norm`` normalises each head's q and k (:class:`RMSNorm` over
    its ``head_dim`` channels, ``norm_eps``), ``rope_theta`` rotates them
    after (:func:`rotary_embedding`), ``output_gate`` multiplies the
    heads' output by ``sigmoid(x W_gate)`` before ``proj``, and
    ``window`` lets a query see only the latest ``window`` keys (causal,
    :func:`blendjax.ops.attention.local_attention`)."""

    num_heads: int
    dtype: Any = None  # None -> the precision policy's compute dtype
    use_ring: bool = False
    mesh: object = None
    seq_axis: str = "seq"
    batch_axis: str = "data"
    causal: bool = False
    sp_mode: str = "ring"  # 'ring' | 'ulysses' (when use_ring=True)
    attn_backend: str = "auto"  # local path: 'auto' | 'flash' | 'xla'
    num_kv_heads: int | None = None  # None -> num_heads, the fused qkv
    head_dim: int | None = None  # None -> C // num_heads
    use_bias: bool = True
    qk_norm: bool = False
    rope_theta: float | None = None
    output_gate: bool = False
    window: int | None = None
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        dtype = default_compute_dtype(self.dtype)
        b, t, c = x.shape
        h = self.num_heads
        d = self.head_dim or c // h
        if (self.num_kv_heads is not None or not self.use_bias or d * h != c
                or self.qk_norm or self.rope_theta is not None
                or self.output_gate or self.window is not None):
            return self._grouped(x, dtype, d)
        # use_ring gates sequence parallelism for back-compat; explicitly
        # requesting the non-default strategy also enables it.
        use_sp = self.use_ring or self.sp_mode == "ulysses"
        # where the fused kernels run at the tokens' own length they
        # read the projection's product packed, with no layout copy
        # between the two (six a layer otherwise): a flat product then
        packed = not use_sp and attention_reads_packed(
            b, t, h, d, dtype, self.attn_backend
        )
        if packed:
            qkv, bias = PackedQKV(h, dtype=dtype, name="qkv")(x)
        else:
            qkv = nn.DenseGeneral(
                (3, h, d), axis=-1, dtype=dtype, param_dtype=jnp.float32,
                name="qkv",
            )(x)
            q, k, v = (qkv[:, :, i] for i in range(3))  # (B, T, H, D)
        assert self.sp_mode in ("ring", "ulysses"), (
            f"unknown sp_mode {self.sp_mode!r}; use 'ring' or 'ulysses'"
        )
        if packed:
            o = local_attention_packed(
                qkv, h, bias=bias, causal=self.causal,
                backend=self.attn_backend,
            )
        elif use_sp:
            # Precision is the kernels' concern: the local path and
            # ulysses' per-device body go through local_attention,
            # whose xla backend does f32 score accumulation + f32
            # softmax with matmul inputs left in the compute dtype
            # (bf16 on the MXU — f32 matmuls run ~4x slower on v5e and
            # halved the bench transformer row's MFU) and whose flash
            # backend is the fused Pallas kernel with the same
            # precision; ring_attention upcasts
            # internally only when it actually rings, because its
            # streaming softmax carries running max/sum in the input
            # dtype.
            assert self.mesh is not None, "sequence parallelism needs a mesh"
            if self.sp_mode == "ulysses":
                o = ulysses_attention(
                    q, k, v, self.mesh, axis=self.seq_axis,
                    causal=self.causal, batch_axis=self.batch_axis,
                    backend=self.attn_backend,
                )
            else:
                o = ring_attention(
                    q, k, v, self.mesh, axis=self.seq_axis,
                    causal=self.causal, batch_axis=self.batch_axis,
                )
        else:
            o = local_attention(q, k, v, causal=self.causal,
                                backend=self.attn_backend)
        o = o.astype(dtype).reshape(b, t, c)
        return nn.Dense(c, dtype=dtype, param_dtype=jnp.float32,
                        name="proj")(o)

    def _grouped(self, x, dtype, d):
        """Separate projections: ``q`` to ``num_heads`` heads, ``k`` and
        ``v`` to ``num_kv_heads``, each key/value head broadcast to the
        query heads that read it, then the three-tensor
        :func:`local_attention` (the fused kernels where ``auto`` takes
        them: one body, K and V repeated in HBM, their gradient summed
        over the group by the broadcast's transpose). The products'
        outputs and the gated heads' output are named for a ``remat``
        policy (:func:`blendjax.utils.metrics.saved_residual`); one that
        lists none of them recomputes them."""
        assert not (self.use_ring or self.sp_mode == "ulysses"), (
            "sequence parallelism takes the fused qkv projection"
        )
        b, t, c = x.shape
        h, kv = self.num_heads, self.num_kv_heads or self.num_heads
        assert h % kv == 0, f"{kv} key/value heads do not divide {h}"

        def project(name, heads, saved):
            return saved_residual(nn.DenseGeneral(
                (heads, d), axis=-1, use_bias=self.use_bias, dtype=dtype,
                param_dtype=jnp.float32, name=name,
            )(x), saved)

        q = project("q", h, RESIDUAL_ATTN_Q)
        k = project("k", kv, RESIDUAL_ATTN_K)
        v = project("v", kv, RESIDUAL_ATTN_V)
        if self.qk_norm:
            q = RMSNorm(self.norm_eps, dtype=dtype, name="q_norm")(q)
            k = RMSNorm(self.norm_eps, dtype=dtype, name="k_norm")(k)
        if self.rope_theta is not None:
            q, k = (rotary_embedding(a, self.rope_theta) for a in (q, k))
        if kv != h:
            metrics.count("attn.path.gqa")
            k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
        o = local_attention(q, k, v, causal=self.causal,
                            backend=self.attn_backend, window=self.window)
        o = o.astype(dtype).reshape(b, t, h * d)
        if self.output_gate:
            gate = saved_residual(nn.Dense(
                h * d, use_bias=self.use_bias, dtype=dtype,
                param_dtype=jnp.float32, name="gate",
            )(x), RESIDUAL_ATTN_GATE)
            o = saved_residual((o.astype(jnp.float32) * nn.sigmoid(
                gate.astype(jnp.float32))).astype(dtype), RESIDUAL_ATTN_GATED)
        return saved_residual(nn.Dense(
            c, use_bias=self.use_bias, dtype=dtype, param_dtype=jnp.float32,
            name="proj",
        )(o), RESIDUAL_ATTN_OUT)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = None  # None -> the precision policy's compute dtype
    use_ring: bool = False
    mesh: object = None
    seq_axis: str = "seq"
    batch_axis: str = "data"
    causal: bool = False
    num_experts: int = 0  # >0: Switch-style MoE MLP (expert parallelism)
    sp_mode: str = "ring"
    attn_backend: str = "auto"

    @nn.compact
    def __call__(self, x):
        dtype = default_compute_dtype(self.dtype)
        c = x.shape[-1]
        y = nn.LayerNorm(dtype=jnp.float32)(x)
        x = x + MultiHeadAttention(
            self.num_heads, dtype=dtype, use_ring=self.use_ring,
            mesh=self.mesh, seq_axis=self.seq_axis,
            batch_axis=self.batch_axis, causal=self.causal,
            sp_mode=self.sp_mode, attn_backend=self.attn_backend,
        )(y)
        y = nn.LayerNorm(dtype=jnp.float32)(x)
        if self.num_experts > 0:
            from blendjax.models.moe import MoEMLP

            y = MoEMLP(
                num_experts=self.num_experts, mlp_ratio=self.mlp_ratio,
                dtype=dtype,
            )(y)
        else:
            y = nn.Dense(c * self.mlp_ratio, dtype=dtype,
                         param_dtype=jnp.float32)(y)
            y = nn.gelu(y)
            y = nn.Dense(c, dtype=dtype, param_dtype=jnp.float32)(y)
        return x + y


class PatchEmbed(nn.Module):
    """Frames ``(B, H, W, C)`` -> patch tokens ``(B, H/p, W/p, features)``:
    the parameters of the ``p`` x ``p`` stride-``p`` ``nn.Conv`` this was
    (``kernel`` ``(p, p, C, features)`` and ``bias``, float32, same
    initializers, so a seeded init and a saved state are unchanged),
    applied by :func:`blendjax.ops.image.embed_patches` as one matrix
    product over the flattened patches. The module's name puts the whole
    input side of the model under one scope in a trace."""

    features: int
    patch: int
    dtype: Any = None  # None -> the precision policy's compute dtype

    @nn.compact
    def __call__(self, images):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (self.patch, self.patch, images.shape[-1], self.features),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,),
            jnp.float32,
        )
        return embed_patches(
            images, kernel, bias, default_compute_dtype(self.dtype)
        )


class StreamFormer(nn.Module):
    """Patchify -> transformer blocks -> head.

    The patch embedding (``patch_embed``, :class:`PatchEmbed`) is a
    matrix product over the flattened ``patch`` x ``patch`` patches of
    the frames, not a strided convolution: with the stream's 4 u8 input
    channels XLA ran the convolution at 19 GB/s on a v5e, the largest
    single operation of the step. Same parameters as the convolution had.

    ``num_outputs=16`` regresses cube corners like
    :class:`~blendjax.models.cnn.CubeRegressor` so it can train on the
    same stream.

    Block params are named ``block{i}`` (stable across the ``remat``
    toggle, which would otherwise rename flax auto-named modules and
    invalidate checkpoints).
    """

    patch: int = 16
    dim: int = 256
    depth: int = 4
    num_heads: int = 8
    num_outputs: int = 16
    dtype: Any = None  # None -> the precision policy's compute dtype
    use_ring: bool = False
    mesh: object = None
    seq_axis: str = "seq"
    batch_axis: str = "data"
    num_experts: int = 0
    moe_every: int = 2  # MoE MLP in every nth block (others stay dense)
    sp_mode: str = "ring"  # sequence-parallel strategy: 'ring' | 'ulysses'
    attn_backend: str = "auto"  # local attention: materialized-scores
    # XLA path for short sequences, the fused Pallas kernel from 24 MiB
    # of f32 scores a call up on a TPU (the crossover measured on the
    # chip; table in blendjax.ops.attention)
    remat: bool = False  # rematerialize blocks: ~O(sqrt) activation
    # memory in backprop for long sequences/deep stacks, recompute on the
    # backward pass (jax.checkpoint via nn.remat — HBM for FLOPs)

    def partition_rules(self):
        """Megatron-style tensor-parallel layout for this param tree
        (:func:`blendjax.parallel.resolve_rules` picks this up when a
        build passes no explicit rules): attention heads column-split
        over ``tp`` on the qkv kernel's heads dim, the output/MLP
        projections row-split, the MLP hidden dim column-split, and
        the vocab-analog output head column-split — composing with
        ``seq`` ring/ulysses attention so longseq runs ``data×tp``.
        The ``fsdp`` axis then takes each leaf's largest free dim
        (generic defaults), so one rule set serves every layout."""
        from blendjax.parallel.sharding import DEFAULT_TP_RULES, PartitionRule

        return DEFAULT_TP_RULES + (
            PartitionRule(r"^Dense_0/kernel$", ("tp",)),  # output head
        )

    @nn.compact
    def __call__(self, images):
        dtype = default_compute_dtype(self.dtype)
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
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.depth):
            moe = (
                self.num_experts
                if self.num_experts > 0 and i % self.moe_every == 0
                else 0
            )
            # Explicit names keep the param tree identical whether or not
            # blocks are rematerialized (nn.remat would otherwise rename
            # Block_i -> remat(CheckpointBlock_i), invalidating
            # checkpoints on a memory-knob toggle).
            x = block_cls(
                self.num_heads, dtype=dtype, use_ring=self.use_ring,
                mesh=self.mesh, seq_axis=self.seq_axis,
                batch_axis=self.batch_axis, num_experts=moe,
                sp_mode=self.sp_mode, attn_backend=self.attn_backend,
                name=f"block{i}",
            )(x)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        x = x.mean(axis=1)
        out = nn.Dense(self.num_outputs, dtype=jnp.float32,
                       param_dtype=jnp.float32)(x)
        return out
