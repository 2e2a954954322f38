"""Thin shard_map wrappers over XLA collectives.

These are the TPU-native replacement for a NCCL/MPI-style backend: the
collectives ride ICI and are inserted/fused by XLA (SURVEY.md §5
"distributed communication backend"). Most code should just annotate
shardings and let pjit insert collectives; these helpers exist for
explicit SPMD regions (ring attention, metrics reduction) and for tests.
"""

from __future__ import annotations

import functools


def _shard_map(fn, mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map`` for every SPMD region in the repo (ring,
    ulysses, pipeline parallel, the sharded tile decode). ``check=False``
    turns off the varying-manual-axes checker, which cannot infer e.g.
    the replication of a tiled all_gather's output."""
    import jax

    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check,
    )


def all_reduce_sum(x, mesh, axis: str = "data"):
    """psum over ``axis``; input sharded on leading dim, result replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    f = _shard_map(
        lambda s: jax.lax.psum(s, axis),
        mesh,
        in_specs=P(axis),
        out_specs=P(),
    )
    return f(x)


def all_reduce_mean(x, mesh, axis: str = "data"):
    import jax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    f = _shard_map(
        lambda s: jax.lax.psum(s, axis) / n,
        mesh,
        in_specs=P(axis),
        out_specs=P(),
    )
    return f(x)


def all_gather(x, mesh, axis: str = "data"):
    """Gather shards of the leading dim onto every device."""
    import jax
    from jax.sharding import PartitionSpec as P

    f = _shard_map(
        lambda s: jax.lax.all_gather(s, axis, axis=0, tiled=True),
        mesh,
        in_specs=P(axis),
        out_specs=P(),
        check=False,
    )
    return f(x)


def ring_permute(x, mesh, axis: str = "seq", shift: int = 1):
    """Rotate shards around the ring: device i's shard moves to i+shift
    (the primitive under ring attention / pipelined collectives)."""
    import jax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    f = _shard_map(
        functools.partial(jax.lax.ppermute, axis_name=axis, perm=perm),
        mesh,
        in_specs=P(axis),
        out_specs=P(axis),
    )
    return f(x)
