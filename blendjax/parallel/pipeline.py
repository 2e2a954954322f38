"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

No reference counterpart (SURVEY.md §2.4: "Pipeline parallelism: none") —
designed TPU-first rather than ported: each device on the ``pipe`` mesh
axis holds ONE stage's parameters (stacked pytree sharded on its leading
axis), and activations flow stage-to-stage over the ICI ring via
``ppermute`` while microbatches fill the pipeline (scaling-book-style
collective-permute pipeline). The whole schedule is a single ``lax.scan``
inside ``shard_map``, so it jits once, differentiates (reverse-mode flows
back through the ppermutes), and composes with ``data``/``tensor`` axes in
an outer pjit.

Schedule: step ``t`` runs microbatch ``m = t - s`` on stage ``s``; the
pipeline drains after ``M + S - 1`` steps (bubble fraction ``(S-1)/(M+S-1)``
— pick ``M >= 4*S`` to amortize).
"""

from __future__ import annotations

import functools


def stack_stage_params(stage_params: list):
    """Stack per-stage parameter pytrees along a new leading axis so the
    result can be sharded on the ``pipe`` mesh axis (leading dim =
    number of stages)."""
    import jax.numpy as jnp
    from jax import tree_util

    return tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params
    )


def _pipeline_local(params, x, *, stage_fn, axis_name: str, n_stages: int,
                    vary_axes: tuple = ()):
    """Per-device body (inside shard_map).

    params: stage pytree with leading dim 1 (this device's stage).
    x: (M, mb, ...) all microbatches (replicated over the pipe axis).
    Returns (M, mb_out...) — final-stage outputs, psum-replicated.
    """
    import jax
    import jax.numpy as jnp
    from jax import tree_util

    s = jax.lax.axis_index(axis_name)
    my_params = tree_util.tree_map(lambda a: a[0], params)
    m_total = x.shape[0]
    # Forward-only neighbor links: stage s -> s+1 (no wraparound; devices
    # with no inbound edge receive zeros, which the schedule masks out).
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    # Probe the output structure once to build the accumulator.
    out_shape = jax.eval_shape(stage_fn, my_params, x[0])

    def step(carry, t):
        buf, out = carry
        # Stage 0 reads fresh microbatch t; later stages read the buffer
        # their predecessor sent last step.
        mb = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, m_total - 1), axis=0, keepdims=False
        )
        inp = jnp.where(s == 0, mb, buf)
        y = stage_fn(my_params, inp)
        # Valid iff this stage is processing a real microbatch this step.
        m = t - s
        valid = (m >= 0) & (m < m_total)
        y = jnp.where(valid, y, jnp.zeros_like(y))
        # Final stage deposits microbatch m into the output slot.
        is_last = s == n_stages - 1
        idx = jnp.clip(m, 0, m_total - 1)
        out = jax.lax.dynamic_update_index_in_dim(
            out,
            jnp.where(valid & is_last, y,
                      jax.lax.dynamic_index_in_dim(out, idx, 0, False)),
            idx, 0,
        )
        buf_next = jax.lax.ppermute(y, axis_name, perm)
        return (buf_next, out), None

    assert out_shape.shape == x.shape[1:], (
        "pipeline stages must be shape-preserving (activation ring buffer): "
        f"stage maps {x.shape[1:]} -> {out_shape.shape}"
    )
    buf0 = jnp.zeros(out_shape.shape, out_shape.dtype)
    out0 = jnp.zeros((m_total,) + out_shape.shape, out_shape.dtype)
    # Constant carries must be marked device-varying for shard_map's VMA
    # type checking (same dance as ring.py).
    buf0, out0 = (
        jax.lax.pcast(a, vary_axes, to="varying") for a in (buf0, out0)
    )

    n_steps = m_total + n_stages - 1
    (_, out), _ = jax.lax.scan(
        step, (buf0, out0), jnp.arange(n_steps)
    )
    # Only the last stage holds real outputs; psum replicates them (every
    # other stage contributes zeros).
    mask = (s == n_stages - 1).astype(out.dtype)
    return jax.lax.psum(out * mask, axis_name)


def pipeline_apply(
    stage_fn,
    stacked_params,
    x,
    mesh,
    axis: str = "pipe",
    batch_axis: str | None = "data",
):
    """Run ``x`` through ``n_stages`` copies of ``stage_fn`` pipelined over
    mesh axis ``axis``.

    Args:
      stage_fn: ``(params, microbatch) -> microbatch_out``; all stages
        share this code (classic GPipe homogeneous stages), and each stage
        must be shape-preserving (the activation ring buffer is reused).
      stacked_params: pytree whose leaves have leading dim = mesh size of
        ``axis`` (one slice per stage; see :func:`stack_stage_params`).
      x: ``(num_microbatches, microbatch, ...)`` input. The microbatch
        dim (dim 1) stays sharded on ``batch_axis`` when that axis exists
        on the mesh, so dp x pp composes without gathering the batch.
      mesh: the device mesh; ``axis`` must be one of its names.

    Returns ``(num_microbatches, microbatch, ...)`` outputs, replicated
    over ``axis`` and sharded on ``batch_axis``. Any other mesh axes are
    treated as replicated inside the pipeline body.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if axis not in mesh.axis_names:
        # Degenerate single-stage mesh: apply stages sequentially.
        import jax.numpy as jnp
        from jax import tree_util

        n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        y = x
        for i in range(n):
            p_i = tree_util.tree_map(lambda a: a[i], stacked_params)
            y = jnp.stack([stage_fn(p_i, y[m]) for m in range(y.shape[0])])
        return y

    n_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        assert leaf.shape[0] == n_stages, (
            f"stacked_params leading dim {leaf.shape[0]} != mesh axis "
            f"'{axis}' size {n_stages}; one stage slice per pipe device"
        )
    b_ax = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    vary_axes = tuple(a for a in (axis, b_ax) if a)
    body = functools.partial(
        _pipeline_local, stage_fn=stage_fn, axis_name=axis,
        n_stages=n_stages, vary_axes=vary_axes,
    )
    xspec = P(None, b_ax)
    from blendjax.parallel.collectives import _shard_map

    f = _shard_map(
        body,
        mesh,
        in_specs=(P(axis), xspec),
        out_specs=xspec,
    )
    return f(stacked_params, x)
