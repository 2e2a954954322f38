"""Mixture-of-Experts MLP with expert parallelism over the ``expert`` axis.

No reference counterpart (SURVEY.md §2.4: "Expert parallelism: none") —
net-new, TPU-first design: Switch-Transformer-style top-1 routing with a
static token capacity so every shape is known at trace time (XLA cannot
tile dynamic shapes onto the MXU), dispatch/combine as einsums against a
one-hot dispatch tensor (MXU-friendly, no gather/scatter), and expert
weights stacked on a leading ``E`` dim that
:func:`blendjax.parallel.sharding.param_sharding_rules` shards over the
``expert`` mesh axis — GSPMD then inserts the all-to-alls between the
data-sharded tokens and expert-sharded weights automatically.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from blendjax.precision import default_compute_dtype
from blendjax.utils.metrics import (
    COUNTERS_COLLECTION,
    RESIDUAL_DENSE_GATE,
    RESIDUAL_DENSE_OUT,
    RESIDUAL_DENSE_UP,
    RESIDUAL_EXPERTS_GATE,
    RESIDUAL_EXPERTS_UP,
    RESIDUAL_SHARED_GATE,
    RESIDUAL_SHARED_UP,
    SCOPE_MOE,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTE,
    SCOPE_MOE_SHARED,
    saved_residual,
)


def collect_aux_loss(intermediates) -> jnp.ndarray:
    """Sum every sown ``aux_loss`` in an ``intermediates`` collection."""
    from jax import tree_util

    total = jnp.zeros(())
    for path, leaf in tree_util.tree_leaves_with_path(intermediates):
        if "aux_loss" in tree_util.keystr(path):
            total = total + jnp.sum(leaf)
    return total


def apply_with_aux(model, variables, *args, aux_weight: float = 1e-2,
                   **kwargs):
    """``model.apply`` that also returns the weighted MoE load-balancing
    loss (Switch aux loss). Add it to the task loss — without it, top-1
    routing can collapse onto one expert. Returns ``(out, aux)``."""
    out, state = model.apply(
        variables, *args, mutable=["intermediates"], **kwargs
    )
    return out, aux_weight * collect_aux_loss(state.get("intermediates", {}))


class MoEMLP(nn.Module):
    """Drop-in replacement for a transformer MLP block.

    Input/output: ``(B, T, C)``. Tokens are routed top-1 to one of
    ``num_experts`` expert MLPs (``C -> C*mlp_ratio -> C``); tokens over a
    expert's capacity are dropped (their residual path passes through
    unchanged, as in Switch Transformer).
    """

    num_experts: int
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    dtype: Any = None  # None -> the precision policy's compute dtype

    @nn.compact
    def __call__(self, x):
        dtype = default_compute_dtype(self.dtype)
        b, t, c = x.shape
        e = self.num_experts
        n = b * t
        cap = max(1, int(self.capacity_factor * n / e))
        tokens = x.reshape(n, c)

        # Router in f32 for a stable softmax.
        logits = nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32,
                          name="router")(tokens.astype(jnp.float32))
        probs = nn.softmax(logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)           # (N,)
        gate = jnp.max(probs, axis=-1)                    # (N,)
        onehot = nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (N, E)

        # Position of each token within its expert's queue; beyond-capacity
        # tokens get dispatch weight 0 (dropped).
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0   # (N, E)
        keep = (pos >= 0) & (pos < cap)
        pos_oh = nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
        dispatch = onehot[..., None] * pos_oh * keep[..., None]  # (N, E, cap)

        # Aux load-balancing loss (Switch eq. 4): mean fraction routed x
        # mean router prob, per expert.
        frac = onehot.mean(axis=0)
        prob_mean = probs.mean(axis=0)
        self.sow("intermediates", "aux_loss", e * jnp.sum(frac * prob_mean))

        # Expert weights stacked on E: sharded over the ``expert`` mesh
        # axis by param_sharding_rules (name-keyed).
        h = c * self.mlp_ratio
        w1 = self.param("expert_wi", nn.initializers.lecun_normal(),
                        (e, c, h), jnp.float32)
        b1 = self.param("expert_bi", nn.initializers.zeros, (e, h),
                        jnp.float32)
        w2 = self.param("expert_wo", nn.initializers.lecun_normal(),
                        (e, h, c), jnp.float32)
        b2 = self.param("expert_bo", nn.initializers.zeros, (e, c),
                        jnp.float32)

        xt = tokens.astype(dtype)
        xe = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype), xt)
        he = nn.gelu(
            jnp.einsum("ecd,edh->ech", xe, w1.astype(dtype))
            + b1[:, None].astype(dtype)
        )
        ye = (jnp.einsum("ech,ehd->ecd", he, w2.astype(dtype))
              + b2[:, None].astype(dtype))
        combine = dispatch * gate[:, None, None]
        y = jnp.einsum("nec,ecd->nd", combine.astype(dtype), ye)
        return y.reshape(b, t, c)


# -- routed experts without drops, one chip's share ----------------------------


def relu2(x):
    return jnp.square(nn.relu(x))


def _swiglu(gate):
    """``up -> silu(gate) * up``: SwiGLU's activation."""
    return lambda up: nn.silu(gate) * up


def feed_forward(x, width: int, dtype, gated: bool, prefix: str = "",
                 residuals: tuple = (None, None)):
    """``W_down relu(x W_up)^2``, or with ``gated`` (SwiGLU)
    ``W_down (silu(x W_gate) * x W_up)``, no bias. The matrices are the
    calling module's Dense layers ``{prefix}up``, ``{prefix}gate`` and
    ``{prefix}down``; ``residuals`` names the up and the gate product's
    outputs for the ``remat`` policy (:func:`saved_residual`)."""

    def dense(out, name):
        return nn.Dense(out, use_bias=False, dtype=dtype,
                        param_dtype=jnp.float32, name=prefix + name)

    def product(name, saved):
        y = dense(width, name)(x)
        return y if saved is None else saved_residual(y, saved)

    hidden = product("up", residuals[0])
    act = _swiglu(product("gate", residuals[1])) if gated else relu2
    return dense(x.shape[-1], "down")(act(hidden))


class SwiGLU(nn.Module):
    """A dense gated feed-forward layer: :func:`feed_forward` with
    ``gated``, its matrices ``gate``, ``up`` and ``down``; the up and the
    gate product's outputs and its own are named for StreamMoEFormer's
    ``remat`` policy."""

    width: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        return saved_residual(feed_forward(
            x, self.width, default_compute_dtype(self.dtype), gated=True,
            residuals=(RESIDUAL_DENSE_UP, RESIDUAL_DENSE_GATE),
        ), RESIDUAL_DENSE_OUT)


class RoutedExperts(nn.Module):
    """An expert layer as the sparse models of 2025 publish it, and one
    chip's share of it: ``(B, T, C) -> (B, T, C)``.

    Every token scores all ``num_experts`` routed experts
    (``sigmoid(x W_r)``, float32), picks the ``experts_per_token`` with
    the largest score plus selection bias (``e_score_correction_bias``:
    read by the selection alone, so its gradient is zero), and weighs
    them by their scores, normalised to sum to 1 and times ``scaling``.
    An expert is ``W_down relu(W_up x)^2``, no gate and no bias, or with
    ``gated`` ``W_down (silu(W_gate x) * W_up x)`` (SwiGLU); a shared
    expert of the same form (``shared_width``) sees every token.

    The module holds the ``experts_held`` experts from ``expert_offset``
    on (all of them by default), stacked on a leading dimension, and
    computes the part of the result they give: what expert parallelism
    leaves on one chip. The picks of absent experts are left out, not
    re-routed. No pick is dropped and no shape depends on the routing:
    every held expert runs over every token, times the token's weight for
    it (0 where it was not chosen), as two plain products whose cost is
    ``experts_held x N`` rows wherever the picks fall. The load a chip
    sees is anywhere between nothing and every pick at a seeded
    initialisation on near-identical tokens (PERF.md, PR 37), so a cost
    that followed the rows would make the step's time the seed's. A
    token's parts are summed in float32.

    Every call also counts where its picks fell, in integers with no
    gradient, and sows three scalars into the ``counters`` collection
    (the step builders return them beside the loss,
    :func:`blendjax.train.steps.counting`): ``rows_held``, the picks
    on the held experts, which a drop-free sparse form would compute
    here; ``rows_busiest_share``, the most picks any one share of
    ``experts_held`` experts gets, the shares being the deployment's
    chips by contiguous blocks of experts, which an expert-parallel
    step waits on; and ``rows_even_share``, a share's even load
    ``N k experts_held / num_experts`` (rounded down).
    """

    num_experts: int
    experts_per_token: int
    expert_width: int
    shared_width: int = 0
    experts_held: int | None = None
    expert_offset: int = 0
    scaling: float = 1.0
    gated: bool = False
    dtype: Any = None  # None -> the precision policy's compute dtype

    @nn.compact
    def __call__(self, x):
        dtype = default_compute_dtype(self.dtype)
        b, t, c = x.shape
        n, k, f = b * t, self.experts_per_token, self.expert_width
        held = self.num_experts if self.experts_held is None else (
            self.experts_held
        )
        router = self.param(
            "router", nn.initializers.lecun_normal(),
            (c, self.num_experts), jnp.float32,
        )
        bias = self.param(
            "e_score_correction_bias", nn.initializers.zeros_init(),
            (self.num_experts,), jnp.float32,
        )
        stacked = nn.initializers.lecun_normal(batch_axis=0)
        w_up = self.param("experts_up", stacked, (held, c, f), jnp.float32)
        w_down = self.param("experts_down", stacked, (held, f, c), jnp.float32)
        w_up, w_down = w_up.astype(dtype), w_down.astype(dtype)
        if self.gated:
            w_gate = self.param(
                "experts_gate", stacked, (held, c, f), jnp.float32
            ).astype(dtype)
        flat = x.reshape(n, c)
        tokens = flat.astype(dtype)

        with jax.named_scope(SCOPE_MOE):
            with jax.named_scope(SCOPE_MOE_ROUTE):
                # a float32 input is routed before its rounding to dtype
                scores = nn.sigmoid(jnp.dot(
                    flat.astype(jnp.float32), router,
                    precision=lax.Precision.HIGHEST,
                ))
                _, experts = lax.top_k(scores + lax.stop_gradient(bias), k)
                self._count(experts, held)
                weights = jnp.take_along_axis(scores, experts, axis=1)
                weights = self.scaling * weights / (
                    weights.sum(axis=1, keepdims=True) + 1e-20
                )
                local = experts[:, :, None] - self.expert_offset
                gate = jnp.sum(
                    jnp.where(local == jnp.arange(held), weights[:, :, None],
                              0.0), axis=1,
                )                                              # (N, held)
            with jax.named_scope(SCOPE_MOE_EXPERTS):
                up = saved_residual(jnp.einsum(
                    "nc,ecf->nef", tokens, w_up,
                    preferred_element_type=jnp.float32,
                ), RESIDUAL_EXPERTS_UP)
                act = relu2
                if self.gated:
                    act = _swiglu(saved_residual(jnp.einsum(
                        "nc,ecf->nef", tokens, w_gate,
                        preferred_element_type=jnp.float32,
                    ), RESIDUAL_EXPERTS_GATE))
                y = jnp.einsum(
                    "nef,efc->nc",
                    (gate[:, :, None] * act(up)).astype(dtype), w_down,
                    preferred_element_type=jnp.float32,
                )
            if self.shared_width:
                with jax.named_scope(SCOPE_MOE_SHARED):
                    y = y + feed_forward(
                        tokens, self.shared_width, dtype, self.gated,
                        prefix="shared_",
                        residuals=(RESIDUAL_SHARED_UP, RESIDUAL_SHARED_GATE),
                    ).astype(jnp.float32)
        return y.astype(dtype).reshape(b, t, c)

    def _count(self, experts, held):
        """Sow how the picks ``experts`` (N, k) fall: see the class."""
        e = self.num_experts
        # the picks on the lanes and an expert a row: with the experts on
        # the lanes instead, each pick is broadcast across them, and on
        # the chip the count took 23.6 us a layer where this takes 2.9
        # (9,600 tokens, 6 of 128; my chip run, PR 39)
        per_expert = jnp.sum(
            experts.T[None] == jnp.arange(e)[:, None, None], axis=(1, 2),
            dtype=jnp.int32,
        )  # what the selection bias's balancing update reads
        shares = -(-e // held)
        per_share = jnp.pad(per_expert, (0, shares * held - e)).reshape(
            shares, held
        ).sum(axis=1)
        lo = self.expert_offset
        for name, value in (
            ("rows_held", per_expert[lo:lo + held].sum()),
            ("rows_busiest_share", per_share.max()),
            ("rows_even_share", jnp.int32(experts.size * held // e)),
        ):
            self.sow(COUNTERS_COLLECTION, name, value)
