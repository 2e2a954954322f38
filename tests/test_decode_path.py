"""Which implementation ``decode_tile_delta`` takes, and that it says so.

Auto-selection asks ``jax.default_backend()``, so these cases answer
"tpu" for it and only *trace* the decode (``jax.make_jaxpr``: tracing a
``pallas_call`` needs no TPU, lowering one would) — the
``tiles.decode_path.*`` counters are bumped at trace time, which is what
is under test. The kernels' results are covered by tests/test_tiles.py
(interpret mode) and on the chip by ``chip_smoke.py``.
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from blendjax.ops.tiles import DECODE_PATHS, decode_tile_delta
from blendjax.utils.metrics import metrics

H, W, C = 64, 128, 4


def _mesh(**axes):
    n = int(np.prod(list(axes.values())))
    return Mesh(
        np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
        tuple(axes),
    )


@pytest.mark.parametrize(
    "tile, batch, mesh_axes, expect",
    [
        # no mesh = a single-device program, however many devices the
        # host has (8 here): the kernel, bare. This used to fall to the
        # XLA scatter whenever jax.device_count() != 1.
        ((16, 32), 8, None, {"pallas_spatial"}),
        (16, 8, None, {"pallas_scatter"}),
        # a geometry neither kernel can tile
        ((4, 4), 8, None, {"xla_scatter"}),
        ((16, 32), 8, {"data": 1}, {"pallas_spatial"}),
        ((16, 32), 8, {"data": 4}, {"pallas_spatial", "shard_map"}),
        (16, 8, {"data": 2, "fsdp": 2}, {"pallas_scatter", "shard_map"}),
        # multi-device mesh without the batch axis: wrapped, replicated
        ((16, 32), 8, {"fsdp": 4}, {"pallas_spatial", "shard_map"}),
        # the batch does not divide over the axis: the one auto fallback
        ((16, 32), 6, {"data": 4}, {"xla_scatter"}),
    ],
)
def test_decode_path_taken_is_counted(
    monkeypatch, tile, batch, mesh_axes, expect
):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    th, tw = (tile, tile) if isinstance(tile, int) else tile
    n = (H // th) * (W // tw)
    mesh = _mesh(**mesh_axes) if mesh_axes else None
    before = dict(metrics.report()["counters"])
    jax.make_jaxpr(
        lambda r, i, t: decode_tile_delta(r, i, t, (H, W, C), mesh=mesh)
    )(
        np.zeros((n, th, tw, C), np.uint8),
        np.zeros((batch, 3), np.int32),
        np.zeros((batch, 3, th, tw, C), np.uint8),
    )
    after = metrics.report()["counters"]
    taken = {
        p for p in DECODE_PATHS
        if after.get(f"tiles.decode_path.{p}", 0)
        > before.get(f"tiles.decode_path.{p}", 0)
    }
    assert taken == expect
