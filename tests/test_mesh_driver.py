"""MeshTrainDriver: the live pipeline on a named mesh.

The load-bearing contract (ISSUE 8 / ROADMAP item 1): sharding is a
LAYOUT choice, never a math change — the same recorded stream through
``MeshTrainDriver`` on a 1-device and an 8-device CPU mesh produces
identical f32 losses (within the repo's established equivalence
tolerance: collective reduction reorders shift the last float32 bits,
wrong sharding math is orders of magnitude away — see
``blendjax.testing.equivalence``), with the one-dispatch-per-step and
donation invariants intact, and exact fresh/echoed accounting when the
echo reservoir rides along.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blendjax.data import StreamDataPipeline
from blendjax.data.echo import EchoingPipeline, SampleReservoir
from blendjax.models import CubeRegressor
from blendjax.parallel import (
    batch_sharding,
    create_mesh,
    ring_sharding,
)
from blendjax.train import MeshTrainDriver
from blendjax.utils.metrics import metrics as reg

# The one bar for "the same losses under another layout", for models
# that compute in f32: a layout re-orders f32 reductions and nothing
# else, which moves a ~3e-1 loss by a few ulps (measured on this stream,
# PR 32: 1 against 8 devices 8.9e-8, data2xfsdp4 0.0, data4xtp2 3.0e-8),
# while wrong sharding math moves the first decimal. chip_smoke.py holds
# its four-chip legs to the same value. A model that computes in bf16
# cannot be held to it (tests/test_resume.py measures why).
F32_EXACT_ATOL = 5e-6

B = 16
HW = 32


def _mesh(n):
    return create_mesh({"data": n}, devices=jax.devices()[:n])


def _messages(n=12, batch=B, seed=0):
    """A deterministic recorded stream: the SAME message sequence every
    call, so two mesh legs consume identical bytes."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        yield {
            "_prebatched": True,
            "btid": 0,
            "image": rng.integers(0, 255, (batch, HW, HW, 4), np.uint8),
            "xy": (rng.random((batch, 8, 2)) * HW).astype(np.float32),
        }


def _model():
    return CubeRegressor(features=(8, 16), dtype=jnp.float32)


def _drive(n_dev, n_msgs=10, **driver_kwargs):
    mesh = _mesh(n_dev)
    drv = MeshTrainDriver.build(
        _model(), mesh, np.zeros((B, HW, HW, 4), np.uint8),
        sync_every=1, inflight=2, **driver_kwargs,
    )
    with StreamDataPipeline(
        _messages(n_msgs), batch_size=B, mesh=mesh
    ) as pipe:
        for sb in pipe:
            drv.submit(sb)
    drv.finish()
    return drv


def test_sharded_vs_single_device_losses_identical():
    """The acceptance gate: same recorded stream, 1-device vs 8-device
    mesh, f32 losses equal step for step."""
    l1 = np.asarray(_drive(1).losses)
    l8 = np.asarray(_drive(8).losses)
    assert l1.shape == l8.shape and len(l1) == 10
    np.testing.assert_allclose(l1, l8, rtol=0, atol=F32_EXACT_ATOL)


def test_mesh_batches_actually_shard_over_data():
    mesh = _mesh(8)
    with StreamDataPipeline(
        _messages(2), batch_size=B, mesh=mesh
    ) as pipe:
        sb = next(iter(pipe))
    assert len(sb["image"].sharding.device_set) == 8
    # every chip holds an equal B/8 slice of the batch
    shard_shapes = {
        s.data.shape for s in sb["image"].addressable_shards
    }
    assert shard_shapes == {(B // 8, HW, HW, 4)}


def test_one_dispatch_per_step_under_sharding():
    reg.reset()
    drv = _drive(8, n_msgs=6)
    spans = reg.report()["spans"]
    assert spans.get("decode.dispatch", {}).get("count", 0) == 0
    assert spans["train.dispatch"]["count"] == drv.steps == 6
    assert drv.dispatches == drv.steps


def test_mesh_step_donation_keeps_state_buffers_stable():
    """Pinned out_shardings + donation: the param buffers never move
    across steps (per-shard pointer equality), so the optimizer state
    is updated in place on every chip."""
    mesh = _mesh(8)
    drv = MeshTrainDriver.build(
        _model(), mesh, np.zeros((B, HW, HW, 4), np.uint8),
        sync_every=0, inflight=1,
    )
    batches = iter(
        StreamDataPipeline(_messages(4), batch_size=B, mesh=mesh)
    )
    drv.submit(next(batches))
    drv.drain()
    leaf = jax.tree_util.tree_leaves(drv.state.params)[0]
    ptrs0 = [
        s.data.unsafe_buffer_pointer() for s in leaf.addressable_shards
    ]
    for sb in batches:
        drv.submit(sb)
    drv.drain()
    leaf = jax.tree_util.tree_leaves(drv.state.params)[0]
    ptrs1 = [
        s.data.unsafe_buffer_pointer() for s in leaf.addressable_shards
    ]
    assert ptrs0 == ptrs1


def test_batch_size_must_divide_mesh_axis():
    with pytest.raises(ValueError, match="divide evenly"):
        StreamDataPipeline(_messages(1), batch_size=12, mesh=_mesh(8))


def test_partial_tail_pads_to_mesh_divisible_bucket():
    """A ragged final batch smaller than the shard count must still
    place: the pad stage restricts its bucket ladder to multiples of
    the batch axis's shard count, so a 3-row tail on an 8-way mesh
    pads to 8 rows + mask instead of crashing device_put."""

    def frames(n=35):
        rng = np.random.default_rng(1)
        for i in range(n):
            yield {
                "btid": 0, "frameid": i,
                "image": rng.integers(0, 255, (HW, HW, 4), np.uint8),
                "xy": (rng.random((8, 2)) * HW).astype(np.float32),
            }

    mesh = _mesh(8)
    with StreamDataPipeline(
        frames(), batch_size=B, mesh=mesh, emit_partial_final=True
    ) as pipe:
        batches = list(pipe)
    assert [int(b["image"].shape[0]) for b in batches] == [B, B, 8]
    tail = batches[-1]
    assert "_mask" in tail and float(np.asarray(tail["_mask"]).sum()) == 3
    assert len(tail["image"].sharding.device_set) == 8


def test_feeder_places_each_batch_in_one_call(monkeypatch):
    """The placement contract BJX111 lints for: ONE grouped device_put
    per batch on a single-host mesh, never a per-field (or worse,
    per-device) loop."""
    from blendjax.data.pipeline import DeviceFeeder

    mesh = _mesh(8)
    feeder = DeviceFeeder(mesh=mesh)
    calls = []
    real = jax.device_put

    def counting(x, *a, **k):
        calls.append(x)
        return real(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting)
    placed = feeder._place({
        "image": np.zeros((B, HW, HW, 4), np.uint8),
        "xy": np.zeros((B, 8, 2), np.float32),
        "weights": np.zeros((B,), np.float32),
        "_meta": [{"btid": 0}],
        "btid": 1,
    })
    assert len(calls) == 1
    assert set(placed) == {"image", "xy", "weights", "_meta", "btid"}
    assert len(placed["image"].sharding.device_set) == 8


def test_mfu_scales_by_participating_chips():
    mesh = _mesh(8)
    drv = MeshTrainDriver.build(
        _model(), mesh, np.zeros((B, HW, HW, 4), np.uint8),
        flops_per_image=1e6, peak_flops_per_chip=1e12,
    )
    assert drv.chips == 8
    assert drv.peak_flops == pytest.approx(8e12)
    stats = drv.stats
    assert stats["chips"] == 8 and stats["processes"] == 1


# -- the fused packed path on a mesh ------------------------------------------


def _tile_messages(n=6, batch=8):
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
        TileDeltaEncoder,
        pack_batch,
    )

    rng = np.random.default_rng(3)
    ref = rng.integers(0, 255, (HW, HW, 4), np.uint8)
    enc = TileDeltaEncoder(ref, tile=(16, 32))
    for k in range(n):
        frames = []
        for i in range(batch):
            img = ref.copy()
            img[8:16, 8:16] = (7 + 13 * i + 29 * k) % 251
            frames.append(img)
        deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
        idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)
        msg = {
            "_prebatched": True, "btid": 0,
            "image" + TILEIDX_SUFFIX: idx,
            "image" + TILES_SUFFIX: tiles,
            "image" + TILESHAPE_SUFFIX: [HW, HW, 4, 16, 32],
            "xy": (np.random.default_rng(k).random((batch, 8, 2)) * HW
                   ).astype(np.float32),
        }
        if k == 0:
            msg["image" + TILEREF_SUFFIX] = ref
        yield msg


def _drive_fused(n_dev, batch=8, chunk=2, n_msgs=6):
    mesh = _mesh(n_dev)
    drv = MeshTrainDriver.build(
        _model(), mesh, np.zeros((batch, HW, HW, 4), np.uint8),
        fused=True, sync_every=1, inflight=2,
    )
    with StreamDataPipeline(
        _tile_messages(n_msgs, batch), batch_size=batch, mesh=mesh,
        chunk=chunk, emit_packed=True,
    ) as pipe:
        for sb in pipe:
            drv.submit(sb)
    drv.finish()
    return drv


def test_fused_mesh_step_one_dispatch_and_loss_equivalence():
    """The docs' headline fused=True path, pinned: still-encoded packed
    tile groups decode INSIDE the train jit on the mesh — ZERO
    standalone decode dispatches, one device call per chunk group —
    and the in-jit re-shard over `data` changes layout, not math
    (1-device vs 8-device losses f32-equal)."""
    reg.reset()
    d1 = _drive_fused(1)
    spans1 = reg.report()["spans"]
    reg.reset()
    d8 = _drive_fused(8)
    spans8 = reg.report()["spans"]
    for spans, drv in ((spans1, d1), (spans8, d8)):
        assert spans.get("decode.dispatch", {}).get("count", 0) == 0
        assert spans["train.dispatch"]["count"] == drv.dispatches == 3
    l1 = np.concatenate([np.ravel(x) for x in d1.losses])
    l8 = np.concatenate([np.ravel(x) for x in d8.losses])
    np.testing.assert_allclose(l1, l8, rtol=0, atol=F32_EXACT_ATOL)


def test_fused_mesh_step_rejects_missing_data_axis():
    from blendjax.train import make_mesh_fused_step, make_train_state

    mesh = _mesh(8)
    state = make_train_state(
        _model(), np.zeros((8, HW, HW, 4), np.uint8), mesh=mesh
    )
    with pytest.raises(ValueError, match="not an axis"):
        make_mesh_fused_step(state, mesh, data_axis="dp")


# -- the echo reservoir under sharding ----------------------------------------


def test_sharded_reservoir_donation_and_layout():
    mesh = _mesh(8)
    res = SampleReservoir(64, augment=None, sharding=ring_sharding(mesh))
    batch = {
        "image": np.ones((B, 8, 8, 4), np.uint8),
        "xy": np.zeros((B, 8, 2), np.float32),
    }
    res.insert(batch)
    ring = res._buffers["image"]
    assert len(ring.sharding.device_set) == 8
    ptrs0 = [
        s.data.unsafe_buffer_pointer() for s in ring.addressable_shards
    ]
    for _ in range(6):
        res.insert(batch)
    ptrs1 = [
        s.data.unsafe_buffer_pointer()
        for s in res._buffers["image"].addressable_shards
    ]
    assert ptrs0 == ptrs1  # donated scatter: stable sharded buffers
    out = res.sample(np.arange(B))
    # drawn batches leave pre-sharded in the batch layout
    assert out["image"].sharding == batch_sharding(mesh)
    assert out["image"].shape == (B, 8, 8, 4)


def test_sharded_reservoir_capacity_must_divide():
    mesh = _mesh(8)
    with pytest.raises(ValueError, match="divide evenly"):
        SampleReservoir(30, sharding=ring_sharding(mesh))


def _echo_leg(n_dev, n_msgs=6, factor=4):
    """One EchoingPipeline run to exhaustion on a mesh: N*B samples,
    echo factor F, capacity >= all samples, N*B*F divisible by B — so
    every sample is drawn exactly F times and the aggregate accounting
    is deterministic regardless of drain-thread timing."""
    mesh = _mesh(n_dev)
    inner = StreamDataPipeline(
        _messages(n_msgs), batch_size=B, mesh=mesh
    )
    echo = EchoingPipeline(
        inner, capacity=n_msgs * B, max_echo_factor=factor,
        augment=None, mesh=mesh, batch_size=B,
    )
    drv = MeshTrainDriver.build(
        _model(), mesh, np.zeros((B, HW, HW, 4), np.uint8),
        sync_every=1, inflight=2,
    )
    with echo:
        for sb in echo:
            drv.submit(sb)
    drv.finish()
    return echo, drv


def test_echo_accounting_exact_on_mesh_and_matches_single_device():
    """Exact fresh/echoed accounting under sharding: run to stream
    exhaustion with capacity >= every sample — each of the N*B samples
    is drawn exactly ``factor`` times, so fresh == inserted and
    fresh + echoed == steps * B EXACTLY, on both mesh sizes."""
    n_msgs, factor = 6, 4
    e1, _ = _echo_leg(1, n_msgs, factor)
    e8, d8 = _echo_leg(8, n_msgs, factor)
    for e in (e1, e8):
        assert e.inserted == n_msgs * B
        assert e.fresh == e.inserted  # every sample first-used
        assert e.fresh + e.echoed == e.steps * B  # exact, per draw
        assert e.steps == n_msgs * factor  # full budget drained
    assert (e1.steps, e1.fresh, e1.echoed) == (e8.steps, e8.fresh, e8.echoed)
    # the driver trained one dispatch per echoed step on the mesh
    assert d8.dispatches == e8.steps


def test_scripted_reservoir_draws_match_across_meshes():
    """Deterministic reservoir script (no drain thread): same inserts,
    same host-chosen draw indices, same seed — the sharded gather +
    mesh step must produce f32-identical losses on 1 and 8 devices."""

    def leg(n_dev):
        mesh = _mesh(n_dev)
        res = SampleReservoir(
            64, augment=None, rng=7,
            sharding=ring_sharding(mesh) if n_dev > 1 else None,
        )
        drv = MeshTrainDriver.build(
            _model(), mesh, np.zeros((B, HW, HW, 4), np.uint8),
            sync_every=1, inflight=1,
        )
        idx_rng = np.random.default_rng(11)
        for hb in _messages(4):
            res.insert({"image": hb["image"], "xy": hb["xy"]})
            for _ in range(2):  # echo factor 2 via scripted draws
                idx = idx_rng.integers(0, res.size, B)
                drv.submit(res.sample(idx))
        drv.finish()
        return np.asarray(drv.losses)

    l1, l8 = leg(1), leg(8)
    np.testing.assert_allclose(l1, l8, rtol=0, atol=F32_EXACT_ATOL)


# -- fleet observability -------------------------------------------------------


def test_process_snapshot_is_tagged_and_gathers_locally():
    from blendjax.obs.fleetview import (
        gather_fleet_snapshots,
        process_snapshot,
    )

    reg.reset()
    snap = process_snapshot(driver={"host_blocks": 0})
    assert snap["process"] == 0 and snap["processes"] == 1
    assert snap["verdict"].startswith("doctor:")
    snaps = gather_fleet_snapshots(driver={"host_blocks": 0})
    assert len(snaps) == 1 and snaps[0]["process"] == 0


def test_fleet_report_aggregates_processes():
    from blendjax.obs.fleetview import fleet_report

    snaps = [
        {
            "process": 0, "processes": 2, "seq_gaps": 1,
            "lineage": {"7": {"received": 10}},
            "trace": {"completed": 3, "unordered": 0},
            "verdict": "doctor: producer-bound — starving (spawn more)",
        },
        {
            "process": 1, "processes": 2, "seq_gaps": 2,
            "lineage": {"7": {"received": 4}},
            "trace": {"completed": 2, "unordered": 1},
            "verdict": "doctor: balanced — no single stage dominates",
        },
    ]
    rep = fleet_report(snaps)
    assert rep["processes"] == 2
    assert rep["seq_gaps"] == 3
    assert rep["trace_completed"] == 5 and rep["trace_unordered"] == 1
    # same btid on two processes stays namespaced, never merged
    assert set(rep["lineage"]) == {"p0/7", "p1/7"}
    assert rep["verdicts"]["p0"].startswith("doctor: producer-bound")
    # the actionable verdict wins the dominant pick over 'balanced'
    assert rep["dominant_verdict"] == "producer-bound"


def test_echo_batch_size_must_divide_mesh_axis():
    """Build-time, not first-draw-time: an EchoingPipeline whose drawn
    batches can't split over the mesh raises a named error instead of
    an opaque XLA shard-divisibility failure inside the draw jit."""
    mesh = _mesh(8)
    inner = StreamDataPipeline(_messages(1, batch=12), batch_size=12)
    with pytest.raises(ValueError, match="divide evenly"):
        EchoingPipeline(inner, capacity=16, mesh=mesh, batch_size=12)


# -- layouts: fsdp/tp legs through the driver ---------------------------------


def _drive_layout(layout, n_msgs=10):
    from blendjax.parallel import resolve_layout

    mesh = resolve_layout(layout).create_mesh()
    drv = MeshTrainDriver.build(
        _model(), mesh, np.zeros((B, HW, HW, 4), np.uint8),
        layout=layout, sync_every=1, inflight=2,
    )
    with StreamDataPipeline(
        _messages(n_msgs), batch_size=B, mesh=mesh
    ) as pipe:
        for sb in pipe:
            drv.submit(sb)
    drv.finish()
    return drv


def test_cross_layout_losses_identical():
    """The tentpole acceptance gate: the SAME recorded stream under
    pure data, data×fsdp, and data×tp layouts trains f32-identically —
    sharding the state is a layout choice, never a math change."""
    base = np.asarray(_drive(8).losses)
    for layout, axis in (("data2xfsdp4", "fsdp"), ("data4xtp2", "tp")):
        drv = _drive_layout(layout)
        losses = np.asarray(drv.losses)
        assert losses.shape == base.shape
        np.testing.assert_allclose(
            base, losses, rtol=0, atol=F32_EXACT_ATOL
        )
        # and the layout actually sharded the state over its model axis
        specs = [
            tuple(p.sharding.spec)
            for p in jax.tree_util.tree_leaves(drv.state.params)
        ]
        assert any(
            axis in jax.tree_util.tree_leaves(s) for s in specs
        ), (layout, specs)


def test_layout_stat_and_dispatch_under_fsdp():
    reg.reset()
    drv = _drive_layout("data2xfsdp4", n_msgs=6)
    assert drv.layout == "data×fsdp"
    assert drv.stats["layout"] == "data×fsdp"
    spans = reg.report()["spans"]
    assert spans["train.dispatch"]["count"] == drv.steps == 6


def test_build_rejects_model_axis_sharded_batch():
    """Satellite gate: an fsdp/tp-sharded BATCH compiles a wrong
    program — build refuses it by name at build time."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from blendjax.parallel import resolve_layout

    mesh = resolve_layout("data4xtp2").create_mesh()
    img = np.zeros((B, HW, HW, 4), np.uint8)
    bad = jax.device_put(img, NamedSharding(mesh, P("tp")))
    with pytest.raises(ValueError, match="tp"):
        MeshTrainDriver.build(
            _model(), mesh, img, layout="data4xtp2",
            aot_batch={"image": bad},
        )


def test_reservoir_rejects_model_axis_ring():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from blendjax.parallel import resolve_layout

    mesh = resolve_layout("data4xtp2").create_mesh()
    with pytest.raises(ValueError, match="tp"):
        SampleReservoir(64, sharding=NamedSharding(mesh, P("tp")))


def test_fsdp_hbm_ledger_fraction():
    """Satellite: the ledger's per-device memory figures
    (memory_analysis of the compiled sharded step) under data×fsdp are
    a ~1/|fsdp| fraction of the replicated layout's — the measured
    basis of the beyond-one-chip HBM contract."""
    from blendjax.obs.devledger import ledger
    from blendjax.parallel import resolve_layout

    def figures(layout):
        reg.reset()
        ledger.reset()
        mesh = resolve_layout(layout).create_mesh()
        bs = batch_sharding(mesh)
        # small spatial geometry so the train STATE (params + adam
        # moments), not conv activations, dominates the peak — the
        # regime the fraction contract speaks to
        img = np.zeros((B, 16, 16, 4), np.uint8)
        MeshTrainDriver.build(
            _model(), mesh, img, layout=layout, aot=True,
            aot_batch={
                "image": jax.device_put(img, bs),
                "xy": jax.device_put(
                    np.zeros((B, 8, 2), np.float32), bs
                ),
            },
            buckets=(B,), sync_every=0, inflight=2,
        )
        g = reg.report()["gauges"]
        return g["device.argument_bytes"], g["device.hbm_peak_bytes"]

    arg_rep, hbm_rep = figures("data8")
    arg_f, hbm_f = figures("data2xfsdp4")
    # argument bytes are state-dominated: ~|fsdp|=4 with slack for the
    # replicated biases and the batch slice; hbm peak adds temps
    assert arg_rep / arg_f > 2.5, (arg_rep, arg_f)
    assert hbm_rep / hbm_f > 2, (hbm_rep, hbm_f)


# -- live producers on the mesh ------------------------------------------------


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_live_producers_feed_the_mesh_one_dispatch_a_step(n_dev):
    """The mesh path off a LIVE stream (the legs above feed arrays): two
    producer processes -> sharded ingest (two workers) -> mesh feeder ->
    ``MeshTrainDriver``, the global batch growing with the mesh at a
    fixed batch a device. Every batch arrives sharded over all of the
    mesh, every step is one dispatch with no decode beside it, and no
    sequence number is skipped."""
    from blendjax.fleet import synthetic_fleet
    from blendjax.obs.lineage import lineage

    b_dev, shape, steps = 2, (16, 16), 6
    gb = b_dev * n_dev
    mesh = _mesh(n_dev)
    reg.reset()
    lineage.reset()
    with synthetic_fleet(
        2, shape=shape, batch=gb, bind_grace_s=0.5
    ) as launcher:
        drv = MeshTrainDriver.build(
            CubeRegressor(features=(4,), dtype=jnp.float32), mesh,
            np.zeros((gb, *shape, 4), np.uint8), sync_every=0, inflight=4,
        )
        with StreamDataPipeline(
            launcher.addresses["DATA"], batch_size=gb, mesh=mesh,
            ingest_workers=2, timeoutms=30_000,
        ) as pipe:
            it = iter(pipe)
            while drv.steps < steps:
                sb = next(it)
                assert sb["image"].shape == (gb, *shape, 4)
                assert len(sb["image"].sharding.device_set) == n_dev
                drv.submit(sb)
            assert np.isfinite(drv.drain())
        for i in launcher.active_indices():
            # a producer told to stop drains into a consumer that has
            # gone, for the launcher's whole five seconds: stop it cold
            launcher.retire_instance(i, drain=False)
    spans = reg.report()["spans"]
    assert drv.dispatches == drv.steps == steps
    assert spans["train.dispatch"]["count"] == steps
    assert "decode.dispatch" not in spans
    assert {"ingest.recv.shard0", "ingest.recv.shard1"} <= set(spans)
    assert len(lineage.report()) == 2 and lineage.total_gaps() == 0
