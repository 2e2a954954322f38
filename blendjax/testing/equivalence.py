"""Shared numeric-equivalence scaffold for sharded parallelism modes.

One implementation of the contract "a sharded step reproduces the
single-device run of the identical model/batch", used by BOTH the test
suite (``tests/test_equivalence.py``) and the driver dry run
(``__graft_entry__.dryrun_multichip``) so the two can never assert
different tolerances. Finiteness alone would pass a wrong-math sharding
rule with a plausible loss; these gates are the self-made ground truth
net-new parallel code needs (SURVEY.md §2.4 implication b).
"""

from __future__ import annotations

import numpy as np


def normalized_spec(sharding) -> tuple:
    """A sharding's PartitionSpec as a plain comparable tuple: some jax
    releases canonicalize spec entries to 1-tuples, so ``P(None,
    'data')`` arrives as ``P(None, ('data',))`` — assertions comparing
    layouts go through this ONE normalizer (the dryrun and the test
    suite must not drift on the next canonicalization quirk)."""
    return tuple(
        e[0] if isinstance(e, tuple) and len(e) == 1 else e
        for e in tuple(getattr(sharding, "spec", sharding))
    )


def max_tree_diff(a, b) -> float:
    """Max abs elementwise difference across two equal-structure trees."""
    import jax

    diffs = jax.tree_util.tree_map(
        lambda x, y: float(np.max(np.abs(np.asarray(x) - np.asarray(y)))),
        a, b,
    )
    return max(jax.tree_util.tree_leaves(diffs))


def loss_and_grads(model, params, images, xy, sharding=None):
    """Corner-MSE loss value + grads for ``model`` on one batch; with
    ``sharding`` the batch is placed on the mesh first (params carry
    their own layouts)."""
    import jax
    import jax.numpy as jnp

    if sharding is not None:
        images = jax.device_put(images, sharding)
        xy = jax.device_put(xy, sharding)

    @jax.jit
    def lg(p):
        def loss(p):
            pred = model.apply({"params": p}, images)
            return jnp.mean((pred.reshape(-1, 8, 2) - xy) ** 2)

        return jax.value_and_grad(loss)(p)

    loss, grads = lg(params)
    return float(loss), jax.tree_util.tree_map(np.asarray, grads)


def assert_sharded_matches_single_device(
    sharded_model,
    single_model,
    mesh,
    images,
    xy,
    tol_loss: float = 1e-5,
    tol_grad: float = 1e-4,
):
    """Same init key -> identical params; assert the sharded model's
    loss/grads match the single-device model's within RELATIVE
    tolerances (collective/reduction reorders shift the last float32
    bits of a ~1e2-magnitude loss; wrong sharding math is orders of
    magnitude away). Returns ``(loss_diff, max_grad_diff)``."""
    import jax

    from blendjax.parallel import batch_sharding
    from blendjax.train import make_train_state

    ref_state = make_train_state(single_model, images)
    sh_state = make_train_state(sharded_model, images, mesh=mesh)
    assert max_tree_diff(ref_state.params, sh_state.params) == 0.0, (
        "ref/sharded init diverged — models are not identical"
    )

    ref_loss, ref_grads = loss_and_grads(
        single_model, ref_state.params, images, xy
    )
    sh_loss, sh_grads = loss_and_grads(
        sharded_model, sh_state.params, images, xy,
        sharding=batch_sharding(mesh),
    )
    loss_diff = abs(sh_loss - ref_loss)
    assert loss_diff < tol_loss * max(1.0, abs(ref_loss)), (
        sh_loss, ref_loss,
    )
    grad_diff = max_tree_diff(ref_grads, sh_grads)
    grad_scale = max(
        float(np.max(np.abs(g)))
        for g in jax.tree_util.tree_leaves(ref_grads)
    )
    assert grad_diff < tol_grad * max(1.0, grad_scale), (
        f"max grad diff {grad_diff} (grad scale {grad_scale})"
    )
    return loss_diff, grad_diff


def moe_per_token_reference(params, x) -> np.ndarray:
    """Dense per-token reference for MoE top-1 routing with ample
    capacity: each token goes through its argmax expert's MLP alone,
    scaled by the gate probability (float32; no capacity drops)."""
    import jax
    import jax.numpy as jnp

    c = x.shape[-1]
    tokens = np.asarray(x, np.float32).reshape(-1, c)
    logits = tokens @ np.asarray(params["router"]["kernel"]) + np.asarray(
        params["router"]["bias"]
    )
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    idx = probs.argmax(-1)
    gate = probs.max(-1)
    # Gather each token's expert weights, then ONE batched pass (a
    # per-token Python loop would pay one device dispatch per token).
    w1 = np.asarray(params["expert_wi"])[idx]   # (N, C, H)
    b1 = np.asarray(params["expert_bi"])[idx]   # (N, H)
    w2 = np.asarray(params["expert_wo"])[idx]   # (N, H, C)
    b2 = np.asarray(params["expert_bo"])[idx]   # (N, C)
    hidden = np.einsum("nc,nch->nh", tokens, w1) + b1
    hidden = np.asarray(jax.nn.gelu(jnp.asarray(hidden)))
    out = np.einsum("nh,nhc->nc", hidden, w2) + b2
    out = gate[:, None] * out
    return out.reshape(np.asarray(x).shape)
