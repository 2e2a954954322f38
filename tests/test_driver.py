"""Async overlap driver + fused decode + bucket padding (PR 3):

- fused full-frame-palette decode+step trains identically to the
  unfused device_stage -> chunked-step pipeline (and dispatches zero
  standalone decode jits),
- mask-padded bucket batches score and backpropagate identically to
  their exact-shape forms (and keep the jit compile cache bounded),
- TrainDriver keeps dispatches in flight with completion tracking:
  host blocks happen only when the ring is genuinely full, and the
  overlap-working case blocks no more than ``inflight`` times per
  epoch.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import optax  # noqa: E402

from blendjax.data.batcher import bucket_sizes, pad_to_bucket  # noqa: E402
from blendjax.train import TrainDriver  # noqa: E402
from blendjax.utils.metrics import metrics as reg  # noqa: E402


# -- shape-bucketed partials -------------------------------------------------


def test_bucket_sizes_ladder():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(6) == (1, 2, 4, 6)
    assert bucket_sizes(1) == (1,)


def test_pad_to_bucket_shapes_and_mask():
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.integers(0, 255, (5, 8, 8, 4), np.uint8),
        "xy": rng.random((5, 8, 2)).astype(np.float32),
        "palette": np.zeros((16, 4), np.uint8),  # non-lead sidecar
        "_meta": [{}] * 5,
        "_partial": True,
    }
    out = pad_to_bucket(batch, batch_size=8)
    assert out["image"].shape == (8, 8, 8, 4)
    assert out["xy"].shape == (8, 8, 2)
    assert out["palette"].shape == (16, 4)  # untouched
    assert "_partial" not in out
    assert out["_mask"].tolist() == [1, 1, 1, 1, 1, 0, 0, 0]
    assert len(out["_meta"]) == 5  # true-length provenance preserved
    np.testing.assert_array_equal(out["image"][:5], batch["image"])
    assert not out["image"][5:].any()  # zero fill


def test_masked_loss_and_grads_match_exact_shape():
    """The acceptance contract: a bucket-padded partial batch must
    produce the same loss AND the same updated params as its
    exact-shape form (mask-weighted mean, true-count denominator)."""
    from blendjax.models import CubeRegressor
    from blendjax.train import make_supervised_step, make_train_state

    rng = np.random.default_rng(3)
    imgs = rng.integers(0, 255, (5, 16, 16, 4), np.uint8)
    xys = (rng.random((5, 8, 2)) * 16).astype(np.float32)
    s0 = make_train_state(
        CubeRegressor(), imgs, optimizer=optax.sgd(0.01)
    )
    step = make_supervised_step(donate=False)

    s_exact, m_exact = step(s0, {"image": imgs, "xy": xys})
    padded = pad_to_bucket(
        {"image": imgs, "xy": xys, "_partial": True}, batch_size=8
    )
    s_pad, m_pad = step(s0, padded)

    np.testing.assert_allclose(
        float(m_exact["loss"]), float(m_pad["loss"]), rtol=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        s_exact.params, s_pad.params,
    )


def test_bucketed_partials_keep_jit_cache_bounded():
    """Distinct tail sizes all land in one masked bucket shape: the
    step compiles once for the full batch and once for the bucket —
    never per ragged tail (the recompile this PR eliminates)."""
    from blendjax.models import CubeRegressor
    from blendjax.train import make_supervised_step, make_train_state

    rng = np.random.default_rng(4)
    full = {
        "image": rng.integers(0, 255, (8, 16, 16, 4), np.uint8),
        "xy": (rng.random((8, 8, 2)) * 16).astype(np.float32),
    }
    s = make_train_state(
        CubeRegressor(), full["image"], optimizer=optax.sgd(0.01)
    )
    step = make_supervised_step(donate=False)
    s, _ = step(s, full)
    for n in (5, 6, 7):
        padded = pad_to_bucket(
            {
                "image": full["image"][:n],
                "xy": full["xy"][:n],
                "_partial": True,
            },
            batch_size=8,
        )
        s, _ = step(s, padded)
    cache_size = getattr(step, "_cache_size", None)
    if cache_size is not None:  # jax-version tolerant
        assert cache_size() == 2, cache_size()


def test_pipeline_pads_partial_final_batches():
    """emit_partial_final tails come out of the pipeline bucket-padded
    with a _mask (pad_partial defaults on); pad_partial=False restores
    the exact ragged tail."""
    from blendjax.data import StreamDataPipeline

    def items(n):
        for i in range(n):
            yield {
                "image": np.full((8, 8, 4), i, np.uint8),
                "xy": np.zeros((8, 2), np.float32),
            }

    with StreamDataPipeline(
        items(7), batch_size=4, emit_partial_final=True
    ) as pipe:
        batches = list(pipe)
    tail = batches[-1]
    assert np.asarray(tail["image"]).shape[0] == 4
    assert np.asarray(tail["_mask"]).tolist() == [1.0, 1.0, 1.0, 0.0]

    with StreamDataPipeline(
        items(7), batch_size=4, emit_partial_final=True,
        pad_partial=False,
    ) as pipe:
        batches = list(pipe)
    assert np.asarray(batches[-1]["image"]).shape[0] == 3
    assert batches[-1].get("_partial") is True


# -- fused full-frame palette decode ----------------------------------------


def _pal_messages(frames, xys, h, w):
    from blendjax.ops.tiles import (
        FRAMEPAL_SUFFIXES,
        FRAMESHAPE_SUFFIX,
        PALETTE_SUFFIX,
        palettize_frames,
    )

    for g in range(len(xys)):
        batch = frames[2 * g: 2 * g + 2]
        packed, pal, bits = palettize_frames(batch)
        yield {
            "_prebatched": True, "btid": 0,
            "image" + FRAMEPAL_SUFFIXES[bits]: packed,
            "image" + PALETTE_SUFFIX: pal,
            "image" + FRAMESHAPE_SUFFIX: np.array(
                [h, w, 4, bits], np.int32
            ),
            "xy": xys[g],
        }


def test_fused_pal_step_matches_decode_then_step():
    """emit_packed + make_fused_tile_step on a full-frame PALETTE
    stream trains bit-identically to the decode-then-chunked-step
    pipeline — and issues ZERO standalone decode.dispatch jits (the
    decode lives inside the train jit)."""
    from blendjax.data import StreamDataPipeline
    from blendjax.models import CubeRegressor
    from blendjax.train import (
        make_chunked_supervised_step,
        make_fused_tile_step,
        make_train_state,
    )

    rng = np.random.default_rng(7)
    h, w = 16, 24
    colors = rng.integers(0, 255, (5, 4), np.uint8)
    frames = colors[rng.integers(0, 5, (8, h, w))]
    xys = (rng.random((4, 2, 8, 2)) * 16).astype(np.float32)

    s0 = make_train_state(
        CubeRegressor(), frames[:2], optimizer=optax.sgd(0.01)
    )

    with StreamDataPipeline(
        _pal_messages(frames, xys, h, w), batch_size=2, chunk=2
    ) as pipe:
        decoded = list(pipe)
    assert [np.asarray(b["image"]).shape for b in decoded] == [
        (2, 2, h, w, 4)
    ] * 2
    chunked = make_chunked_supervised_step(donate=False)
    s_ref, ref_losses = s0, []
    for b in decoded:
        s_ref, m = chunked(s_ref, {"image": b["image"], "xy": b["xy"]})
        ref_losses.extend(np.asarray(m["loss"]).tolist())

    reg.reset()
    with StreamDataPipeline(
        _pal_messages(frames, xys, h, w), batch_size=2, chunk=2,
        emit_packed=True,
    ) as pipe:
        packed_batches = list(pipe)
    assert all("_pal" in b and "_packed" in b for b in packed_batches)
    fused = make_fused_tile_step(donate=False)
    s_fused, fused_losses = s0, []
    for b in packed_batches:
        s_fused, m = fused(s_fused, b)
        fused_losses.extend(np.asarray(m["loss"]).tolist())
    assert "decode.dispatch" not in reg.spans()

    np.testing.assert_allclose(fused_losses, ref_losses, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-8
        ),
        s_ref.params, s_fused.params,
    )


def test_fused_pal_emit_packed_chunk1_groups_k1():
    """chunk=1 + emit_packed still routes pal batches through the
    packed form (K'=1 groups), so the fused path never needs a
    chunked pipeline to eliminate the decode dispatch."""
    from blendjax.data import StreamDataPipeline

    rng = np.random.default_rng(9)
    h, w = 16, 24
    colors = rng.integers(0, 255, (3, 4), np.uint8)
    frames = colors[rng.integers(0, 3, (8, h, w))]
    xys = (rng.random((4, 2, 8, 2)) * 16).astype(np.float32)
    with StreamDataPipeline(
        _pal_messages(frames, xys, h, w), batch_size=2, chunk=1,
        emit_packed=True,
    ) as pipe:
        batches = list(pipe)
    assert len(batches) == 4
    for b in batches:
        assert "_pal" in b
        assert np.asarray(b["_packed"]).shape[0] == 1  # K'=1


# -- TrainDriver -------------------------------------------------------------


class _FakeLoss:
    """Stand-in for a dispatched loss array with a controllable
    readiness flag (jax.block_until_ready passes non-array leaves
    through untouched, so blocking on one is a no-op)."""

    def __init__(self, ready: bool):
        self._ready = ready

    def is_ready(self) -> bool:
        return self._ready


def _fake_step(ready: bool):
    def step(state, batch):
        return state + 1, {"loss": _FakeLoss(ready)}

    return step


def test_driver_overlap_blocks_at_most_inflight_times():
    """The acceptance contract: with overlap working (dispatches
    complete before the ring refills), the driver performs no more
    than ``inflight`` genuine host blocks per epoch — here zero."""
    drv = TrainDriver(
        _fake_step(ready=True), state=0, inflight=4, sync_every=0
    )
    for _ in range(64):
        drv.submit({"x": np.zeros(1)})
    stats = drv.stats
    assert stats["dispatches"] == 64
    assert stats["host_blocks"] <= drv.inflight
    assert stats["inflight_hwm"] <= drv.inflight


def test_driver_blocks_only_when_ring_genuinely_full():
    """Never-completing dispatches: the driver must bound the ring by
    blocking on the oldest entry — once per submit past the window,
    never more (no per-step serialization)."""
    drv = TrainDriver(
        _fake_step(ready=False), state=0, inflight=4, sync_every=0
    )
    for _ in range(12):
        drv.submit({"x": np.zeros(1)})
    stats = drv.stats
    assert stats["inflight_hwm"] == 4
    assert stats["host_blocks"] == 12 - 4  # one per ring-full submit
    assert stats["dispatches"] == 12


def test_driver_sync_every_and_finish_collect_losses():
    from blendjax.models import CubeRegressor
    from blendjax.train import make_supervised_step, make_train_state

    rng = np.random.default_rng(11)
    batch = {
        "image": rng.integers(0, 255, (8, 16, 16, 4), np.uint8),
        "xy": (rng.random((8, 8, 2)) * 16).astype(np.float32),
    }
    s0 = make_train_state(
        CubeRegressor(), batch["image"], optimizer=optax.sgd(0.01)
    )
    step = make_supervised_step(donate=False)
    drv = TrainDriver(step, s0, inflight=3, sync_every=4)
    for _ in range(8):
        drv.submit(dict(batch))
    state, final = drv.finish()
    assert isinstance(final, float) and np.isfinite(final)
    # 2 periodic syncs + the final drain
    assert len(drv.losses) == 3
    assert int(state.step) == 8
    # drain is idempotent once the ring is empty
    assert drv.drain() == final


def test_driver_pads_unmasked_partials():
    """A `_partial` batch that reaches the driver unmasked (pipeline
    configured with pad_partial=False, or hand-fed) is bucket-padded
    defensively, so it cannot recompile the step mid-run."""
    seen_shapes = []

    def step(state, batch):
        seen_shapes.append(batch["image"].shape)
        assert "_mask" in batch
        return state, {"loss": _FakeLoss(True)}

    drv = TrainDriver(step, state=0, inflight=2, sync_every=0)
    rng = np.random.default_rng(1)
    drv.submit({
        "image": rng.integers(0, 255, (5, 8, 8, 4), np.uint8),
        "xy": np.zeros((5, 8, 2), np.float32),
        "_partial": True,
    })
    assert seen_shapes == [(8, 8, 8, 4)]


def test_driver_run_drives_fused_pipeline_one_dispatch_per_step():
    """End to end: pipeline(emit_packed) -> fused step -> driver. The
    fused training path issues exactly ONE device dispatch per driver
    step and zero standalone decode dispatches."""
    from blendjax.data import StreamDataPipeline
    from blendjax.models import CubeRegressor
    from blendjax.train import make_fused_tile_step, make_train_state

    rng = np.random.default_rng(21)
    h, w = 16, 24
    colors = rng.integers(0, 255, (5, 4), np.uint8)
    frames = colors[rng.integers(0, 5, (8, h, w))]
    xys = (rng.random((4, 2, 8, 2)) * 16).astype(np.float32)
    s0 = make_train_state(
        CubeRegressor(), frames[:2], optimizer=optax.sgd(0.01)
    )
    reg.reset()
    step = make_fused_tile_step(donate=False)
    drv = TrainDriver(step, s0, inflight=2, sync_every=0)
    with StreamDataPipeline(
        _pal_messages(frames, xys, h, w), batch_size=2, chunk=2,
        emit_packed=True,
    ) as pipe:
        state, final = drv.run(pipe)
    assert drv.stats["steps"] == 2  # 4 batches in 2 chunk groups
    spans = reg.spans()
    assert spans["train.dispatch"]["count"] == drv.stats["dispatches"]
    assert "decode.dispatch" not in spans
    assert isinstance(final, float) and np.isfinite(final)


def test_driver_device_timeline_and_mfu_land_in_report():
    """Acceptance: a live driver run populates train.step_device_ms
    percentiles and (given flops_per_image + peak_flops) a train.mfu
    gauge in Metrics.report() — MFU as an always-on run metric, not a
    bench artifact."""
    from blendjax.models import CubeRegressor
    from blendjax.train import make_supervised_step, make_train_state

    rng = np.random.default_rng(13)
    batch = {
        "image": rng.integers(0, 255, (8, 16, 16, 4), np.uint8),
        "xy": (rng.random((8, 8, 2)) * 16).astype(np.float32),
    }
    s0 = make_train_state(
        CubeRegressor(), batch["image"], optimizer=optax.sgd(0.01)
    )
    reg.reset()
    drv = TrainDriver(
        make_supervised_step(donate=False), s0, inflight=2,
        sync_every=0, flops_per_image=1e9, peak_flops=197e12,
    )
    for _ in range(6):
        drv.submit(dict(batch))
    drv.finish()
    report = reg.report()
    h = report["histograms"]["train.step_device_ms"]
    assert h["count"] == 6  # every ring entry retired exactly once
    for q in ("p50", "p95", "p99"):
        assert h[q] >= 0, h
    assert drv.stats["images_retired"] == 6 * 8
    # whole-run MFU published at the drain barrier (short runs would
    # otherwise end inside the 1s gauge window)
    assert report["gauges"]["train.mfu"] > 0
    # without the flops hints the gauge is absent, the histogram stays
    reg.reset()
    drv2 = TrainDriver(
        make_supervised_step(donate=False), s0, inflight=2, sync_every=0
    )
    drv2.submit(dict(batch))
    drv2.finish()
    report = reg.report()
    assert "train.mfu" not in report["gauges"]
    assert report["histograms"]["train.step_device_ms"]["count"] == 1


def _rle_wire_frames(n, B, H, W):
    """``n`` prebatched messages of run-heavy frames as run-length "ndr"
    wire frames at one pinned cap (one packed spec, one compile)."""
    from blendjax.transport.wire import encode_message

    frames = []
    for i in range(n):
        img = np.zeros((B, H, W, 4), np.uint8)
        img[:, 4 + i:14 + i, 6:22] = (i % 3) + 1
        xy = np.full((B, 8, 2), float(i % 9), np.float32)
        frames.append(encode_message(
            {"btid": 0, "_prebatched": True, "image": img, "xy": xy},
            compress_rle=True, rle_cap=128, compress_min_bytes=512,
        ))
    return frames


@pytest.mark.parametrize("chunk", [1, 2])
def test_deferred_rle_trains_the_same_losses_as_host_inflate(chunk):
    """The SAME recorded "ndr" wire bytes, expanded inside the fused
    dispatch (``defer_rle``) or inflated on the host, placed the same
    way, train identical f32 losses update for update: device
    decompression changes where the bytes expand, never what the step
    computes. At ``chunk=2`` the deferred frames ride the palette path's
    K-group program (two scanned updates a dispatch) while inflated raw
    frames cannot be grouped and keep K'=1."""
    import jax

    from blendjax.data import StreamDataPipeline
    from blendjax.models.cnn import CubeRegressor
    from blendjax.train.steps import make_fused_tile_step, make_train_state
    from blendjax.transport.wire import decode_message

    B, H, W = 4, 32, 32
    frames = _rle_wire_frames(6, B, H, W)

    def run(deferred):
        msgs = [decode_message(f, defer_rle=deferred) for f in frames]
        assert ("image__ndr" in msgs[0]) == deferred
        pipe = StreamDataPipeline(
            iter(msgs), batch_size=B, chunk=chunk, emit_packed=True,
            place_in_driver=True,
        )
        state = make_train_state(
            CubeRegressor(), np.zeros((B, H, W, 4), np.uint8),
            rng=jax.random.key(0),
        )
        sink = []
        fused = make_fused_tile_step()

        def step(state, batch):
            state, m = fused(state, batch)
            sink.append(m["loss"])  # the K losses of every dispatch
            return state, m

        drv = TrainDriver(
            step, state, inflight=2, sync_every=0, place=pipe.feeder.place
        )
        with pipe:
            for b in pipe:
                drv.submit(b)
        drv.finish()
        assert drv.steps == len(frames) // (chunk if deferred else 1)
        return np.concatenate([np.asarray(v).reshape(-1) for v in sink])

    reg.reset()
    on_device = run(True)
    assert "decode.dispatch" not in reg.report()["spans"]
    on_host = run(False)
    assert on_device.dtype == np.float32 and on_device.shape == (len(frames),)
    np.testing.assert_array_equal(on_device, on_host)


def test_driver_place_mode_matches_feeder_path():
    """Lever 3 (placement folded into the dispatch): a pipeline in
    place_in_driver mode yields HOST batches, the driver commits the
    grouped device_put at submit, and the trained result is identical
    to the feeder-staged path — with the `feed.place` span now counted
    per submit and zero standalone decode dispatches."""
    import jax

    from blendjax.data import StreamDataPipeline
    from blendjax.models.cnn import CubeRegressor
    from blendjax.train.steps import make_fused_tile_step, make_train_state
    from blendjax.transport.wire import decode_message

    B, H, W = 4, 32, 32
    frames = _rle_wire_frames(6, B, H, W)

    def run(place_in_driver):
        msgs = [
            decode_message(f, defer_rle=place_in_driver) for f in frames
        ]
        pipe = StreamDataPipeline(
            iter(msgs), batch_size=B, emit_packed=True,
            place_in_driver=place_in_driver,
        )
        model = CubeRegressor()
        state = make_train_state(
            model, np.zeros((B, H, W, 4), np.uint8),
            rng=jax.random.key(0),
        )
        drv = TrainDriver(
            make_fused_tile_step(), state, inflight=2, sync_every=0,
            place=pipe.feeder.place if place_in_driver else None,
        )
        with pipe:
            for b in pipe:
                drv.submit(b)
        _, loss = drv.finish()
        return drv, float(loss)

    reg.reset()
    drv_a, loss_a = run(True)
    report = reg.report()
    assert report["spans"]["feed.place"]["count"] == drv_a.steps
    assert "decode.dispatch" not in report["spans"]
    drv_b, loss_b = run(False)
    assert drv_a.steps == drv_b.steps == 6
    assert loss_a == loss_b
