"""Ulysses-style all-to-all sequence parallelism.

The second context-parallel strategy next to :mod:`blendjax.parallel.ring`
(no reference counterpart — blendtorch has no sequence models, SURVEY.md
§2.4): instead of rotating K/V blocks around the ICI ring, two
``all_to_all`` collectives re-shard the tensors between a
*sequence-sharded* layout (B, T/n, H, D) and a *head-sharded* layout
(B, T, H/n, D). Attention itself then runs entirely locally over the full
sequence for the device's head slice — one collective before and one
after, instead of ``n`` ppermute steps.

Trade-off vs ring attention (both exact):

- Ulysses moves Q, K, V and O once each (4 tensor volumes over the ICI
  all-to-all) and needs ``num_heads % n == 0``; compute is a plain local
  attention, so it composes with any masking/attention variant for free.
- Ring moves K and V ``n-1`` times (2(n-1)/n volumes) but keeps the
  sequence axis sharded *through* the softmax, so per-device activation
  memory stays O(T/n) — the long-context scaling story. Ulysses peaks at
  O(T·H/n) for the attention scores.

Use ring for maximum context length, Ulysses when head count is large and
the mask/attention variant is exotic.
"""

from __future__ import annotations

import functools


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale,
                   backend: str):
    """Per-device body (inside shard_map). Local shapes (B, T/n, H, D)."""
    import jax

    from blendjax.ops.attention import local_attention

    # Head-scatter / sequence-gather: split the head axis n ways, deliver
    # chunk j to device j, concatenate the received sequence blocks in
    # device (= global sequence) order -> (B, T, H/n, D).
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    qg, kg, vg = (a2a(x, split_axis=2, concat_axis=1) for x in (q, k, v))
    # The local attention here sees the FULL sequence (for its head
    # slice) — exactly the regime where the fused backend pays: long-T
    # Ulysses composes all-to-alls with the Pallas kernel under 'auto'.
    o = local_attention(qg, kg, vg, causal=causal, scale=scale,
                        backend=backend)
    # Inverse: sequence-scatter / head-gather back to (B, T/n, H, D).
    return a2a(o, split_axis=1, concat_axis=2)


def ulysses_attention(
    q,
    k,
    v,
    mesh,
    axis: str = "seq",
    causal: bool = False,
    scale: float | None = None,
    batch_axis: str | None = "data",
    backend: str = "auto",
):
    """Exact multi-head attention with the sequence dim sharded on
    ``axis``, via head-scatter/sequence-gather all-to-alls.

    Inputs/outputs are (B, T, H, D) global arrays with T sharded on
    ``axis`` (same contract as :func:`~blendjax.parallel.ring_attention`);
    requires ``H % mesh.shape[axis] == 0``. ``backend`` selects the
    per-device local attention after the all-to-all
    (:func:`blendjax.ops.attention.local_attention`). Note the policy
    input there is the POST-all-to-all shape — each device attends the
    full sequence for H/n heads, so the bytes of scores one call would
    materialise shrink by the axis size: ``auto`` takes the fused
    kernel when that per-head-subset figure reaches
    ``FLASH_RESIDUAL_BYTES`` and the full sequence's K/V of a head fit
    VMEM (16k keys), and the materialized path otherwise (pass
    ``backend="flash"`` to force the kernel).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    d = q.shape[-1]
    scale = scale if scale is not None else d**-0.5
    if axis not in mesh.axis_names:
        from blendjax.ops.attention import local_attention

        return local_attention(q, k, v, causal=causal, scale=scale,
                               backend=backend)
    n = mesh.shape[axis]
    h = q.shape[2]
    assert h % n == 0, (
        f"ulysses needs num_heads ({h}) divisible by the '{axis}' axis "
        f"size ({n}); use ring_attention otherwise"
    )
    b_ax = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    spec = P(b_ax, axis)
    body = functools.partial(
        _ulysses_local, axis_name=axis, causal=causal, scale=scale,
        backend=backend,
    )
    from blendjax.parallel.collectives import _shard_map

    # check=False: the fused kernel's pallas_call carries no varying-
    # mesh-axes annotation, which the VMA checker requires
    f = _shard_map(
        body, mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check=False,
    )
    return f(q, k, v)
