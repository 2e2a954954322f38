"""Ring attention: exact attention over sequences sharded across devices.

Sequence/context parallelism has no reference counterpart (SURVEY.md §5
"long-context: absent") and is designed TPU-first: the sequence axis is
sharded over the ``seq`` mesh axis; each device holds local Q/K/V blocks
and K/V blocks rotate around the ICI ring via ``ppermute`` while a
numerically-stable streaming softmax (flash-attention style running
max/sum) accumulates the exact result — compute on block *i* overlaps the
transfer of block *i+1* (XLA overlaps the ppermute with the einsums).

Memory per device is O(T/n) for activations, enabling context lengths n x
longer than a single chip holds.
"""

from __future__ import annotations

import functools

NEG_INF = -1e30


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, scale,
                          vary_axes: tuple = ()):
    """Per-device body (inside shard_map). Shapes: q (B, Tq, H, D);
    k/v (B, Tk, H, D) — the *local* sequence shards."""
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = my * tq + jnp.arange(tq)  # global query positions

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        # K/V block currently held arrived from device (my - i) mod n.
        # Inputs stay in their wire dtype (bf16 halves the ppermute
        # bytes vs the old pre-shard_map f32 upcast); the MXU matmuls
        # ACCUMULATE in f32 via preferred_element_type, and the
        # streaming-softmax carries (o, m, l) are f32 throughout — the
        # numerical risk lives in accumulation, not in the operands.
        src = (my - i) % n
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_cur,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            k_pos = src * tk + jnp.arange(tk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - m_new)
        corr = jnp.where(m <= NEG_INF / 2, 0.0, corr)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_cur.dtype), v_cur,
            preferred_element_type=jnp.float32,
        )
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt

    o = jnp.zeros((b, h, tq, d), jnp.float32)
    m = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)
    # Constant-initialized carries must be marked device-varying to match
    # the loop body's types under shard_map's VMA checking.
    o, m, l = (
        jax.lax.pcast(x, vary_axes, to="varying") for x in (o, m, l)
    )
    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o, m, l, k, v))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    # back to (B, Tq, H, D), in the wire dtype (f32 in -> f32 out)
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    mesh,
    axis: str = "seq",
    causal: bool = False,
    scale: float | None = None,
    batch_axis: str | None = "data",
):
    """Exact multi-head attention with the sequence dim sharded on
    ``axis``. Inputs/outputs are (B, T, H, D) global arrays (T sharded).

    Also usable inside an outer pjit: apply to arrays whose sharding
    matches ``P(batch_axis, axis, None, None)``.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    d = q.shape[-1]
    scale = scale if scale is not None else d**-0.5
    if axis not in mesh.axis_names:
        # No sequence axis on this mesh: nothing to ring over — run plain
        # exact attention (same math, zero collectives; it keeps bf16
        # inputs on the MXU and does its softmax in f32 internally).
        return reference_attention(q, k, v, causal=causal, scale=scale)
    # Inputs enter shard_map in their OWN dtype: the old pre-shard_map
    # f32 upcast doubled the bytes every K/V ppermute hop moved over
    # ICI for bf16 models — the dominant ring cost. Numerical safety
    # lives inside the body instead: f32 score accumulation via
    # preferred_element_type and f32 streaming-softmax carries (see
    # _ring_attention_local), so bf16 in/bf16 out now rings at half the
    # wire bytes with the same f32 accumulation the reference path uses.
    from blendjax.parallel.collectives import _shard_map

    b_ax = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    spec = P(b_ax, axis)
    vary_axes = tuple(a for a in (b_ax, axis) if a in mesh.axis_names)
    body = functools.partial(
        _ring_attention_local, axis_name=axis, causal=causal, scale=scale,
        vary_axes=vary_axes,
    )
    f = _shard_map(
        body, mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return f(q, k, v)


def reference_attention(q, k, v, causal: bool = False, scale=None):
    """Single-device exact attention for testing/fallback (B,T,H,D).

    Mixed precision: both matmuls run in the INPUT dtype (bf16 inputs
    keep the MXU at full rate — f32 matmuls cost ~4x on v5e and held
    the bench transformer row at half its MFU) while scores accumulate
    and the softmax computes in f32, which is where the numerical risk
    actually lives. f32 inputs behave exactly as before.
    """
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = scale if scale is not None else d**-0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
