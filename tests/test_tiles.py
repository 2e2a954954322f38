"""Tile-delta stream encoding (blendjax.ops.tiles): exact reconstruction,
native/numpy agreement, packing buckets, and the end-to-end sparse
streaming path through StreamDataPipeline on the virtual CPU mesh."""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from blendjax.ops.tiles import (  # noqa: E402
    TILE,
    TileDeltaEncoder,
    decode_tile_delta,
    pack_batch,
    tile_grid,
    tile_ref,
)

PRODUCER = os.path.join(
    os.path.dirname(__file__), "..", "examples", "datagen", "cube_producer.py"
)
FALLING = os.path.join(
    os.path.dirname(__file__), "..", "examples", "datagen",
    "falling_cubes_producer.py",
)


def _frames(n=6, shape=(64, 96), seed=0):
    """Reference + frames that sparsely edit random tiles of it."""
    rng = np.random.default_rng(seed)
    h, w = shape
    ref = rng.integers(0, 255, (h, w, 4), np.uint8)
    frames = []
    for _ in range(n):
        img = ref.copy()
        for _ in range(rng.integers(0, 5)):
            y, x = rng.integers(0, h - 8), rng.integers(0, w - 8)
            img[y : y + 8, x : x + 8] = rng.integers(0, 255, (8, 8, 4))
        frames.append(img)
    return ref, frames


@pytest.mark.parametrize("native", [True, False])
def test_roundtrip_exact(native):
    if native and os.environ.get("BLENDJAX_NO_NATIVE") == "1":
        pytest.skip("native disabled")
    ref, frames = _frames()
    enc = TileDeltaEncoder(ref, tile=16)
    if not native:
        enc._native = None
    elif enc._native is None:
        pytest.skip("no toolchain")
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    out = np.asarray(
        decode_tile_delta(tile_ref(ref, 16), idx, tiles, ref.shape)
    )
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(out[i], f)


def test_native_matches_numpy():
    ref, frames = _frames(seed=3)
    enc_n = TileDeltaEncoder(ref, tile=16)
    if enc_n._native is None:
        pytest.skip("no toolchain")
    enc_p = TileDeltaEncoder(ref, tile=16)
    enc_p._native = None
    for f in frames:
        i1, t1 = enc_n.encode(f)
        i1, t1 = i1.copy(), t1.copy()
        i2, t2 = enc_p.encode(f)
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(t1, t2)


def test_identical_frame_encodes_empty_and_full_change_encodes_all():
    ref, _ = _frames()
    enc = TileDeltaEncoder(ref, tile=16)
    idx, _tiles = enc.encode(ref.copy())
    assert len(idx) == 0
    inv = (255 - ref).astype(np.uint8)
    idx, _tiles = enc.encode(inv)
    assert len(idx) == enc.num_tiles


def test_pack_batch_buckets_and_sentinel():
    ref, frames = _frames()
    enc = TileDeltaEncoder(ref, tile=16)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles, bucket=16)
    kmax = max(len(i) for i, _ in deltas)
    assert idx.shape[1] == max(-(-kmax // 16) * 16, 16)
    assert idx.shape[1] <= enc.num_tiles
    for i, (fi, _) in enumerate(deltas):
        assert (idx[i, len(fi):] == enc.num_tiles).all()  # sentinel padding
    assert tiles.shape == (len(frames), idx.shape[1], 16, 16, 4)


def test_decode_rgb_tiles_reconstructs_alpha_from_ref():
    """Channel-sliced tiles (alpha-static streams) still decode exactly."""
    ref, frames = _frames(seed=7)
    # Make alpha static: copy ref's alpha into every frame.
    frames = [np.dstack([f[..., :3], ref[..., 3]]) for f in frames]
    enc = TileDeltaEncoder(ref, tile=16)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    out = np.asarray(
        decode_tile_delta(
            tile_ref(ref, 16), idx, np.ascontiguousarray(tiles[..., :3]),
            ref.shape,
        )
    )
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(out[i], f)


def test_tile_grid_requires_divisibility():
    assert tile_grid((64, 96, 4), 16) == (4, 6)
    with pytest.raises(ValueError):
        tile_grid((65, 96, 4), 16)


def test_decode_sharded_on_mesh():
    """Batch-sharded idx/tiles + replicated ref decode shard-locally."""
    ref, frames = _frames(n=8)
    enc = TileDeltaEncoder(ref, tile=16)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    mesh = Mesh(np.array(jax.devices()).reshape(-1), axis_names=("data",))
    bsh = NamedSharding(mesh, P("data"))
    rsh = NamedSharding(mesh, P())
    out = jax.jit(decode_tile_delta, static_argnames=("shape",))(
        jax.device_put(tile_ref(ref, 16), rsh),
        jax.device_put(idx, bsh),
        jax.device_put(tiles, bsh),
        shape=ref.shape,
    )
    assert out.shape == (8, *ref.shape)
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(np.asarray(out[i]), f)


def test_stream_pipeline_tile_encoding_end_to_end():
    """One producer with --encoding tile -> bit-exact full frames on
    device, verified against a local re-render of the same seeded scene
    (single producer + PUSH FIFO => frames arrive in order)."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.producer.sim import CubeScene

    mesh = Mesh(np.array(jax.devices()).reshape(-1), axis_names=("data",))
    sharding = NamedSharding(mesh, P("data"))
    seed = 5
    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=1,
        named_sockets=["DATA"],
        seed=seed,
        instance_args=[
            ["--shape", "64", "64", "--batch", "8", "--encoding", "tile",
             "--tile", "16"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"],
            batch_size=8,
            sharding=sharding,
            timeoutms=30_000,
        ) as pipe:
            it = iter(pipe)
            batches = [next(it) for _ in range(3)]

    # Re-render the same deterministic stream locally (launcher hands the
    # instance seed+0; frames play 1, 2, 3, ...).
    scene = CubeScene(shape=(64, 64), seed=seed)
    local = {}
    for f in range(1, 8 * len(batches) + 1):
        scene.step(f)
        local[f] = scene.render().copy()

    for b in batches:
        assert b["image"].shape == (8, 64, 64, 4)
        assert b["image"].dtype == np.uint8
        assert b["image"].sharding.is_equivalent_to(sharding, 4)
        img = np.asarray(b["image"])
        fids = np.asarray(b["frameid"])
        for i, f in enumerate(fids):
            np.testing.assert_array_equal(img[i], local[int(f)])


def test_falling_cubes_tile_stream():
    """The reusable TileBatchPublisher path on a second scene/producer."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher

    with PythonProducerLauncher(
        script=FALLING,
        num_instances=1,
        named_sockets=["DATA"],
        seed=2,
        instance_args=[
            ["--shape", "64", "64", "--encoding", "tile", "--batch", "4",
             "--num-cubes", "3"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"], batch_size=4, timeoutms=30_000
        ) as pipe:
            it = iter(pipe)
            batches = [next(it) for _ in range(2)]
    for b in batches:
        assert b["image"].shape == (4, 64, 64, 4)
        assert b["xy"].shape == (4, 3, 2)
        img = np.asarray(b["image"])
        assert img.any()  # cubes rendered, not just background


def test_tile_publisher_direct_pack_overflow_and_flush():
    """The direct-pack fast path (pinned capacity): frames encode
    straight into the batch arrays, a frame exceeding the capacity grows
    it mid-batch (migrating packed rows), a partial flush ships the
    filled prefix — all bit-exact on host-side decode."""
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILESHAPE_SUFFIX,
        decode_tile_delta_np,
        pop_tile_payload,
        expand_palette_tiles_np,
    )
    from blendjax.producer.tile_publisher import TileBatchPublisher

    class Capture:
        def __init__(self):
            self.msgs = []

        def publish(self, **kw):
            self.msgs.append(kw)

    rng = np.random.default_rng(6)
    ref = rng.integers(0, 255, (64, 64, 4), np.uint8)
    cap = Capture()
    pub = TileBatchPublisher(cap, ref, batch_size=3, tile=16,
                             alpha_slice=False, capacity=2)
    frames = []
    # frame edits: 1 tile, then 5 tiles (overflow: 2 -> 32... clamped to
    # num_tiles=16), then 2, then 1 (partial batch -> flush)
    for ntiles in (1, 5, 2, 1):
        img = ref.copy()
        for j in range(ntiles):
            ty, tx = divmod(j, 4)
            img[ty * 16: ty * 16 + 4, tx * 16: tx * 16 + 4] = rng.integers(
                0, 255, (4, 4, 4), np.uint8
            )
        frames.append(img)
        pub.add(img, frameid=np.int64(len(frames)))
    pub.flush()
    assert len(cap.msgs) == 2  # one full batch of 3 + flushed tail of 1
    for msg, batch in zip(cap.msgs, (frames[:3], frames[3:])):
        msg = dict(msg)
        idx = msg.pop("image" + TILEIDX_SUFFIX)
        geom = msg.pop("image" + TILESHAPE_SUFFIX)
        tiles = pop_tile_payload(msg, "image", geom, expand_palette_tiles_np)
        out = decode_tile_delta_np(ref, idx, tiles, tile=16)
        assert len(out) == len(batch)
        for got, want in zip(out, batch):
            np.testing.assert_array_equal(got, want)
    # capacity grew past the overflow and stayed 32-aligned (clamped to
    # the 16-tile grid)
    assert pub._capacity == 16


def test_tile_publisher_fused_engages_for_rgb_default_config():
    """3-channel streams have no alpha plane, so the default
    alpha_slice=True is inert and must not disable the fused path; the
    shipped palette is zero-padded past the used entries."""
    from blendjax.ops.tiles import PALETTE_SUFFIX
    from blendjax.producer.tile_publisher import TileBatchPublisher

    class Capture:
        def __init__(self):
            self.msgs = []

        def publish(self, **kw):
            self.msgs.append(kw)

    ref = np.zeros((32, 32, 3), np.uint8)
    cap = Capture()
    pub = TileBatchPublisher(cap, ref, batch_size=2, tile=16, capacity=4)
    assert pub._fused_ok
    img = ref.copy()
    img[0:8, 0:8] = (1, 2, 3)
    pub.add(img)
    pub.add(img)
    (msg,) = cap.msgs
    from blendjax.ops.tiles import TILEPAL2_SUFFIX

    pal = msg["image" + PALETTE_SUFFIX]
    # <=4 colors per frame => 2-bit indices ship (the densest form)
    packed = msg["image" + TILEPAL2_SUFFIX]
    # per-frame palettes: one (cap, C) table per batch row
    assert pal.ndim == 3 and pal.shape[0] == 2
    for row_pal, row_packed in zip(pal, packed):
        # highest palette index any pixel references bounds the used
        # entries; everything past it must be zero (wire contract —
        # stale table rows must never ship)
        hi = int(max(
            (row_packed >> 6).max(), ((row_packed >> 4) & 3).max(),
            ((row_packed >> 2) & 3).max(), (row_packed & 3).max(),
        ))
        assert hi >= 1  # bg + the edited square's color
        assert (row_pal[hi + 1:] == 0).all()


def test_tile_publisher_raw_direct_pack_path():
    """palette=False: the direct-pack raw path (no fused palettizer)
    ships copied raw tiles, bit-exact, with reused batch arrays."""
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILES_SUFFIX,
        decode_tile_delta_np,
    )
    from blendjax.producer.tile_publisher import TileBatchPublisher

    class Capture:
        def __init__(self):
            self.msgs = []

        def publish(self, **kw):
            self.msgs.append(kw)

    rng = np.random.default_rng(14)
    ref = rng.integers(0, 255, (64, 64, 4), np.uint8)
    cap = Capture()
    pub = TileBatchPublisher(cap, ref, batch_size=2, tile=16,
                             alpha_slice=False, palette=False, capacity=4)
    assert not pub._fused_ok
    frames = []
    for n in range(4):
        img = ref.copy()
        img[0:8, 0:8] = rng.integers(0, 255, (8, 8, 4), np.uint8)
        frames.append(img)
        pub.add(img)
    assert len(cap.msgs) == 2
    # reused batch arrays must not alias the shipped tiles
    assert cap.msgs[0]["image" + TILES_SUFFIX].base is not pub._batch_tiles
    for msg, batch in zip(cap.msgs, (frames[:2], frames[2:])):
        out = decode_tile_delta_np(
            ref, msg["image" + TILEIDX_SUFFIX],
            msg["image" + TILES_SUFFIX], tile=16,
        )
        for got, want in zip(out, batch):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("alpha_slice", [False, True], ids=["fused", "two-pass"])
def test_tile_publisher_index_width_is_sticky_and_pinnable(alpha_slice):
    """The palette index width is a wire shape (a wider batch breaks the
    consumer's chunk group and compiles its own step), so like the
    capacity it only grows — and ``palette_bits`` pins where it starts.
    Both publish paths; frames still decode bit-exact."""
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEPAL_SUFFIXES,
        TILESHAPE_SUFFIX,
        decode_tile_delta_np,
        expand_palette_tiles_np,
        pop_tile_payload,
    )
    from blendjax.producer.tile_publisher import TileBatchPublisher

    class Capture:
        def __init__(self):
            self.msgs = []

        def publish(self, **kw):
            self.msgs.append(kw)

    ref = np.zeros((32, 64, 4), np.uint8)
    ref[..., 3] = 255

    def frame(n_colors):
        img = ref.copy()
        for j in range(n_colors - 1):  # the background is one color
            img[2 * j: 2 * j + 2, 0:8, :3] = 10 * (j + 1)
        return img

    def widths(palette_bits, colors_per_batch):
        cap = Capture()
        pub = TileBatchPublisher(
            cap, ref, batch_size=2, tile=(16, 32), capacity=4,
            alpha_slice=alpha_slice, palette_bits=palette_bits,
        )
        assert pub._fused_ok != alpha_slice
        sent = []
        for n in colors_per_batch:
            pub.add(frame(2))
            pub.add(frame(n))
            sent += [frame(2), frame(n)]
        out = []
        for msg in cap.msgs:
            msg = dict(msg)
            out.append(next(
                b for b, suf in TILEPAL_SUFFIXES.items() if "image" + suf in msg
            ))
            idx = msg.pop("image" + TILEIDX_SUFFIX)
            geom = msg.pop("image" + TILESHAPE_SUFFIX)
            tiles = pop_tile_payload(msg, "image", geom, expand_palette_tiles_np)
            got = decode_tile_delta_np(ref, idx, tiles, tile=(16, 32))
            for g in got:
                np.testing.assert_array_equal(g, sent.pop(0))
        return out

    assert widths(2, [2, 3, 5, 2, 3]) == [2, 2, 4, 4, 4]
    assert widths(4, [2, 3, 5, 2]) == [4, 4, 4, 4]
    with pytest.raises(ValueError, match="palette_bits"):
        TileBatchPublisher(Capture(), ref, batch_size=2, palette_bits=3)


def test_tile_publisher_fused_palette_overflow_falls_back():
    """A frame pushing the persistent stream palette past 256 colors
    latches the fused path off mid-batch; already-packed rows
    reconstruct from their indices (lossless) and the batch ships raw
    tiles — everything still decodes bit-exact."""
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILESHAPE_SUFFIX,
        decode_tile_delta_np,
        expand_palette_tiles_np,
        pop_tile_payload,
    )
    from blendjax.producer.tile_publisher import TileBatchPublisher

    class Capture:
        def __init__(self):
            self.msgs = []

        def publish(self, **kw):
            self.msgs.append(kw)

    rng = np.random.default_rng(15)
    ref = np.zeros((64, 64, 4), np.uint8)
    cap = Capture()
    pub = TileBatchPublisher(cap, ref, batch_size=2, tile=16,
                             alpha_slice=False, capacity=8)
    assert pub._fused_ok
    flat = ref.copy()
    flat[0:16, 0:16] = (10, 20, 30, 255)  # few colors: fused packs it
    rich = ref.copy()
    rich[0:32, 0:32] = rng.integers(0, 255, (32, 32, 4), np.uint8)  # ~1k
    pub.add(flat)
    pub.add(rich)  # overflow mid-batch -> raw fallback for THIS batch
    assert pub._fused_ok  # one overflow does not latch fused off
    # one miss from the fused overflow + one from the publish-time
    # two-pass palettize also failing on the color-rich batch
    assert pub._palette_misses == 2
    pub.add(flat)
    pub.add(flat)  # next batch: fused again (per-batch table reset)
    assert len(cap.msgs) == 2
    for msg, batch in zip(cap.msgs, ((flat, rich), (flat, flat))):
        msg = dict(msg)
        idx = msg.pop("image" + TILEIDX_SUFFIX)
        geom = msg.pop("image" + TILESHAPE_SUFFIX)
        tiles = pop_tile_payload(
            msg, "image", geom, expand_palette_tiles_np
        )
        out = decode_tile_delta_np(ref, idx, tiles, tile=16)
        for got, want in zip(out, batch):
            np.testing.assert_array_equal(got, want)
    # batch 1 shipped raw tiles (overflow), batch 2 palette again
    from blendjax.ops.tiles import TILEPAL2_SUFFIX, TILES_SUFFIX

    assert "image" + TILES_SUFFIX in cap.msgs[0]
    # <=4 colors => the 2-bit palette form ships
    assert "image" + TILEPAL2_SUFFIX in cap.msgs[1]
    assert pub._palette_misses == 0  # success resets the miss latch


def test_tile_producer_partial_tail_flush():
    """--frames not a multiple of --batch: trailing frames still arrive
    (ragged prebatched passthrough)."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher

    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=1,
        named_sockets=["DATA"],
        seed=9,
        instance_args=[
            ["--shape", "64", "64", "--batch", "8", "--frames", "12",
             "--encoding", "tile", "--tile", "16"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"], batch_size=8, timeoutms=30_000,
            max_items=2,
        ) as pipe:
            batches = list(pipe)
    sizes = sorted(b["image"].shape[0] for b in batches)
    assert sizes == [4, 8]
    got = sorted(
        int(f) for b in batches for f in np.asarray(b["frameid"])
    )
    assert got == list(range(1, 13))


def test_pack_unpack_fields_dtypes_roundtrip():
    """Packed single-transfer form reconstructs every supported dtype
    exactly (float64 value-cast to f32 like device_put canonicalization,
    bools as bytes, signed bytes bitcast)."""
    from blendjax.ops.tiles import pack_fields, unpack_fields

    fields = {
        "u8": np.random.randint(0, 255, (4, 3, 3), np.uint8),
        "i8": np.random.randint(-128, 127, (5,), np.int8),
        "f32": np.random.randn(2, 7).astype(np.float32),
        "f64": np.array([1.5, -2.25, 1e6]),
        "i64": np.array([1, -7, 2**31 - 1], np.int64),
        "bool": np.array([True, False, True]),
        "i32": np.arange(6, dtype=np.int32).reshape(2, 3),
    }
    buf, spec = pack_fields(fields)
    assert buf.dtype == np.uint8 and buf.ndim == 1
    out = jax.jit(unpack_fields, static_argnames=("spec",))(buf, spec)
    np.testing.assert_array_equal(np.asarray(out["u8"]), fields["u8"])
    np.testing.assert_array_equal(np.asarray(out["i8"]), fields["i8"])
    np.testing.assert_array_equal(np.asarray(out["f32"]), fields["f32"])
    np.testing.assert_array_equal(
        np.asarray(out["f64"]), fields["f64"].astype(np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(out["i64"]), fields["i64"].astype(np.int32)
    )
    np.testing.assert_array_equal(np.asarray(out["bool"]), fields["bool"])
    np.testing.assert_array_equal(np.asarray(out["i32"]), fields["i32"])


def test_pack_fields_overflowing_int64_raises():
    """Integer narrowing is range-checked: a time_ns-style sidecar value
    that doesn't fit 32 bits raises instead of silently wrapping."""
    from blendjax.ops.tiles import pack_fields

    with pytest.raises(ValueError, match="do not fit"):
        pack_fields({"t_ns": np.array([1_722_000_000_000_000_000], np.int64)})
    with pytest.raises(ValueError, match="do not fit"):
        pack_fields({"u": np.array([2**33], np.uint64)})


def test_pack_fields_keeps_64bit_under_x64():
    """With jax_enable_x64, device_put would keep 64 bits — the packed
    path must match the raw-frame path bit for bit, so no narrowing."""
    from blendjax.ops.tiles import pack_fields, unpack_fields

    big = np.array([2**40, -(2**40)], np.int64)
    jax.config.update("jax_enable_x64", True)
    try:
        buf, spec = pack_fields({"big": big})
        out = jax.jit(unpack_fields, static_argnames=("spec",))(buf, spec)
        np.testing.assert_array_equal(np.asarray(out["big"]), big)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_pack_batch_padding_is_zeroed():
    ref, frames = _frames()
    enc = TileDeltaEncoder(ref, tile=16)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles, bucket=16)
    for i, (fi, _) in enumerate(deltas):
        assert (tiles[i, len(fi):] == 0).all()


def test_record_then_replay_tile_stream_bit_exact(tmp_path):
    """A recorded tile-delta stream replays through the full device
    pipeline with no producers running, bit-exact vs a local re-render
    (SURVEY.md §5 checkpoint/resume: record/replay is the stream's
    checkpoint analog — it must compose with the sparse encoding)."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.producer.sim import CubeScene

    prefix = str(tmp_path / "rec")
    seed = 3
    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=1,
        named_sockets=["DATA"],
        seed=seed,
        instance_args=[
            ["--shape", "64", "64", "--batch", "8", "--frames", "16",
             "--encoding", "tile", "--tile", "16"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"], batch_size=8, timeoutms=30_000,
            max_items=2, record_path_prefix=prefix,
            # a dead producer then raises with its exit code instead of
            # an opaque 30s timeout (this test flaked under heavy
            # machine load; make the failure mode diagnosable)
            launcher=launcher,
        ) as pipe:
            live = list(pipe)
    assert len(live) == 2

    replayed = list(
        StreamDataPipeline.from_recording(f"{prefix}_00.bjr", batch_size=8)
    )
    assert len(replayed) == 2

    scene = CubeScene(shape=(64, 64), seed=seed)
    local = {}
    for f in range(1, 17):
        scene.step(f)
        local[f] = scene.render().copy()
    for b in replayed:
        img = np.asarray(b["image"])
        for i, f in enumerate(np.asarray(b["frameid"])):
            np.testing.assert_array_equal(img[i], local[int(f)])


def test_encode_hint_matches_full_scan():
    """A hint rect covering everything that differs from the ref yields
    the identical delta as the full scan (native and numpy paths)."""
    from blendjax.producer.sim import CubeScene

    scene = CubeScene(shape=(64, 96), seed=4)
    ref = scene.background_image()
    for native in (True, False):
        enc = TileDeltaEncoder(ref, tile=16)
        if not native:
            enc._native = None
        elif enc._native is None:
            continue
        for f in range(1, 6):
            scene.step(f)
            img = scene.render()
            full = tuple(a.copy() for a in enc.encode(img))
            hinted = enc.encode(img, hint=scene.raster.last_drawn)
            np.testing.assert_array_equal(hinted[0], full[0])
            np.testing.assert_array_equal(hinted[1], full[1])
        # degenerate hint: empty rect -> empty delta
        i, t = enc.encode(ref.copy(), hint=(5, 5, 0, 0))
        assert len(i) == 0 and len(t) == 0


def test_rect_tiles_roundtrip_all_decoders():
    """Rectangular (16, 32) tiles — the geometry whose tile row spans
    exactly 128 lanes at C=4, unlocking the direct-spatial Pallas decode
    — encode identically on the native and numpy paths and reconstruct
    bit-exactly through the XLA scatter, the spatial kernel (interpret
    mode off-TPU), and the host-side numpy decoder."""
    from blendjax.ops.tiles import decode_tile_delta_np

    ref, frames = _frames(n=5, shape=(64, 96), seed=23)
    enc = TileDeltaEncoder(ref, tile=(16, 32))
    enc_np = TileDeltaEncoder(ref, tile=(16, 32))
    enc_np._native = None
    assert enc.grid == (4, 3) and enc.num_tiles == 12
    deltas = []
    for f in frames:
        fi, ft = (a.copy() for a in enc.encode(f))
        if enc._native is not None:
            ni, nt = enc_np.encode(f)
            np.testing.assert_array_equal(fi, ni)
            np.testing.assert_array_equal(ft, nt)
        deltas.append((fi, ft))
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    assert tiles.shape[2:] == (16, 32, 4)
    rt = tile_ref(ref, (16, 32))
    xla = np.asarray(
        decode_tile_delta(rt, idx, tiles, ref.shape, use_pallas=False)
    )
    spatial = np.asarray(
        decode_tile_delta(rt, idx, tiles, ref.shape, use_pallas=True)
    )
    host = decode_tile_delta_np(ref, idx, tiles)
    np.testing.assert_array_equal(xla, spatial)
    np.testing.assert_array_equal(xla, host)
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(spatial[i], f)


def test_spatial_decode_empty_capacity_and_identical_frames():
    """Spatial-kernel edge cases: K=0 capacity returns pure reference
    frames; all-sentinel rows (identical frames at nonzero capacity)
    also reconstruct as the reference."""
    rng = np.random.default_rng(29)
    ref = rng.integers(0, 255, (32, 64, 4), np.uint8)
    rt = tile_ref(ref, (16, 32))
    n = 2 * 2
    b = 3
    idx0 = np.empty((b, 0), np.int32)
    tiles0 = np.empty((b, 0, 16, 32, 4), np.uint8)
    out0 = np.asarray(
        decode_tile_delta(rt, idx0, tiles0, ref.shape, use_pallas=True)
    )
    idx_s = np.full((b, 2), n, np.int32)  # all sentinels
    tiles_s = np.zeros((b, 2, 16, 32, 4), np.uint8)
    out_s = np.asarray(
        decode_tile_delta(rt, idx_s, tiles_s, ref.shape, use_pallas=True)
    )
    for bi in range(b):
        np.testing.assert_array_equal(out0[bi], ref)
        np.testing.assert_array_equal(out_s[bi], ref)


def test_sharded_spatial_decode_on_mesh():
    """The direct-spatial kernel survives scale-out the same way the
    slot scatter does: shard_map over the mesh's data axis, bit-exact
    against the XLA path on the virtual 8-device mesh."""
    from blendjax.parallel import create_mesh

    mesh = create_mesh({"data": -1})
    ref, frames = _frames(n=8, shape=(64, 64), seed=31)
    enc = TileDeltaEncoder(ref, tile=(16, 32))
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    rt = tile_ref(ref, (16, 32))
    sharded = np.asarray(
        decode_tile_delta(
            rt, idx, tiles, ref.shape, use_pallas=True, mesh=mesh
        )
    )
    xla = np.asarray(
        decode_tile_delta(rt, idx, tiles, ref.shape, use_pallas=False)
    )
    np.testing.assert_array_equal(sharded, xla)
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(sharded[i], f)


def test_channel_sliced_tiles_take_kernel_paths():
    """Alpha-sliced (RGB-of-RGBA) streams stay kernel-eligible: the
    decode restores the missing channel from the reference on device
    and runs the spatial (rect) or slot (square) kernel — bit-exact vs
    the XLA path that handles Ct < C natively."""
    for tile in ((16, 32), 16):
        ref, frames = _frames(n=4, shape=(64, 64), seed=37)
        # make alpha static so slicing is valid: frames share ref alpha
        for f in frames:
            f[..., 3] = ref[..., 3]
        enc = TileDeltaEncoder(ref, tile=tile)
        deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
        idx, tiles = pack_batch(deltas, enc.num_tiles)
        rgb = np.ascontiguousarray(tiles[..., :3])
        rt = tile_ref(ref, tile)
        xla = np.asarray(
            decode_tile_delta(rt, idx, rgb, ref.shape, use_pallas=False)
        )
        kern = np.asarray(
            decode_tile_delta(rt, idx, rgb, ref.shape, use_pallas=True)
        )
        np.testing.assert_array_equal(xla, kern)
        for i, f in enumerate(frames):
            np.testing.assert_array_equal(kern[i], f)
    # forcing the kernel on an ineligible geometry fails loudly instead
    # of silently measuring the XLA path
    ref8 = np.zeros((64, 64, 4), np.uint8)
    with pytest.raises(ValueError, match="kernel-eligible"):
        decode_tile_delta(
            tile_ref(ref8, 8), np.zeros((1, 1), np.int32),
            np.zeros((1, 1, 8, 8, 4), np.uint8), ref8.shape,
            use_pallas=True,
        )


def test_tileshape_wire_geom_roundtrip():
    """Wire-geometry helpers: the square v1 4-element form and the
    rectangular 5-element form round-trip through geom_tile."""
    from blendjax.ops.tiles import geom_tile, tile_hw, tileshape_wire

    assert tileshape_wire(64, 96, 4, 16) == [64, 96, 4, 16]
    assert tileshape_wire(64, 96, 4, (16, 16)) == [64, 96, 4, 16]
    assert tileshape_wire(64, 96, 4, (16, 32)) == [64, 96, 4, 16, 32]
    assert geom_tile((64, 96, 4, 16)) == (16, 16)
    assert geom_tile((64, 96, 4, 16, 32)) == (16, 32)
    assert tile_hw(16) == (16, 16)
    assert tile_hw((8, 32)) == (8, 32)
    with pytest.raises(ValueError):
        tile_hw((1, 2, 3))


def test_rect_tile_publisher_end_to_end_wire():
    """TileBatchPublisher with rectangular tiles ships the 5-element
    __tileshape form (fused per-frame-palette path included) and the
    shared consumer helpers reconstruct bit-exact frames."""
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILESHAPE_SUFFIX,
        decode_tile_delta_np,
        expand_palette_tiles_np,
        pop_tile_payload,
    )
    from blendjax.producer.sim import CubeScene
    from blendjax.producer.tile_publisher import TileBatchPublisher

    class Capture:
        def __init__(self):
            self.msgs = []

        def publish(self, **kw):
            self.msgs.append(kw)

    scene = CubeScene(shape=(64, 96), seed=7)
    ref = scene.background_image()
    cap = Capture()
    pub = TileBatchPublisher(cap, ref, batch_size=4, tile=(16, 32),
                             alpha_slice=False, capacity=6)
    frames = []
    for f in range(1, 5):
        scene.step(f)
        img = scene.render()
        frames.append(img.copy())
        pub.add(img, frameid=np.int64(f))
    assert len(cap.msgs) == 1
    msg = dict(cap.msgs[0])
    geom = tuple(int(v) for v in msg.pop("image" + TILESHAPE_SUFFIX))
    assert geom == (64, 96, 4, 16, 32)
    idx = msg.pop("image" + TILEIDX_SUFFIX)
    tiles = pop_tile_payload(msg, "image", geom, expand_palette_tiles_np)
    assert tiles.shape[2:] == (16, 32, 4)
    out = decode_tile_delta_np(ref, idx, tiles)
    for got, want in zip(out, frames):
        np.testing.assert_array_equal(got, want)


def test_pallas_scatter_decode_matches_xla_scatter():
    """The Pallas scalar-prefetch scatter kernel (interpret mode off-TPU)
    reconstructs identically to the XLA .at[].set path."""
    ref, frames = _frames(n=4, shape=(64, 64), seed=13)
    enc = TileDeltaEncoder(ref, tile=16)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    rt = tile_ref(ref, 16)
    a = np.asarray(
        decode_tile_delta(rt, idx, tiles, ref.shape, use_pallas=False)
    )
    b = np.asarray(
        decode_tile_delta(rt, idx, tiles, ref.shape, use_pallas=True)
    )
    np.testing.assert_array_equal(a, b)
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(b[i], f)


def test_sharded_pallas_scatter_decode_on_mesh():
    """The shard_map-partitioned Pallas decode (each device scatters its
    local batch shard against the replicated reference) is bit-identical
    to the XLA scatter on the virtual 8-device mesh — VERDICT r1 item 6:
    the fast decode survives multi-device scale-out."""
    from blendjax.parallel import create_mesh

    mesh = create_mesh({"data": -1})
    n = int(np.prod(list(mesh.shape.values())))
    assert n == 8  # conftest forces 8 virtual CPU devices
    ref, frames = _frames(n=8, shape=(64, 64), seed=17)
    enc = TileDeltaEncoder(ref, tile=16)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    rt = tile_ref(ref, 16)

    sharded = np.asarray(
        decode_tile_delta(
            rt, idx, tiles, ref.shape, use_pallas=True, mesh=mesh
        )
    )
    xla = np.asarray(
        decode_tile_delta(rt, idx, tiles, ref.shape, use_pallas=False)
    )
    np.testing.assert_array_equal(sharded, xla)
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(sharded[i], f)

    # auto-select: multi-device without a mesh stays on the XLA path;
    # with a mesh whose axis divides B it takes the sharded Pallas path
    # on TPU (off-TPU auto-select is always False; decide statically)
    from blendjax.data import StreamDataPipeline

    pipe = StreamDataPipeline(iter(()), batch_size=8, sharding=None)
    assert pipe.tiles._decode_mesh() == (None, "data")


def test_pipeline_decode_mesh_resolves_from_sharding():
    """StreamDataPipeline threads (mesh, axis) from its batch sharding
    into the decode jit, so the sharded Pallas path engages on meshes."""
    from blendjax.data import StreamDataPipeline
    from blendjax.parallel import batch_sharding, create_mesh

    mesh = create_mesh({"data": -1})
    pipe = StreamDataPipeline(
        iter(()), batch_size=8, sharding=batch_sharding(mesh)
    )
    got_mesh, axis = pipe.tiles._decode_mesh()
    assert got_mesh is mesh and axis == "data"


def test_multihost_tile_stream_assembles_and_decodes_globally():
    """Tile streams x multihost (VERDICT r1 item 4): batch-leading tile
    fields assemble into global arrays (degenerate 1-process case of
    make_array_from_process_local_data), refs replicate globally, and
    the decode runs shard-locally on the mesh — bit-exact, raw-tile and
    per-row-palette wire variants both."""
    from blendjax.data import StreamDataPipeline
    from blendjax.ops.tiles import (
        PALETTE_SUFFIX,
        TILEIDX_SUFFIX,
        TILEPAL4_SUFFIX,
        TILEPAL8_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
        palettize_tiles,
    )
    from blendjax.parallel import batch_sharding, create_mesh

    mesh = create_mesh({"data": -1})
    sharding = batch_sharding(mesh)
    # Flat background + solid-color edits: the changed tiles then hold
    # few distinct colors, so the palette wire variant engages.
    rng = np.random.default_rng(9)
    ref = np.full((32, 32, 4), (40, 80, 120, 255), np.uint8)
    colors = rng.integers(0, 255, (8, 4), np.uint8)
    frames = []
    for i in range(16):
        img = ref.copy()
        y, x = rng.integers(0, 24, 2)
        img[y: y + 8, x: x + 8] = colors[i % 8]
        frames.append(img)
    enc = TileDeltaEncoder(ref, tile=16)

    def tile_msg(batch, with_ref, palette):
        deltas = [tuple(a.copy() for a in enc.encode(f)) for f in batch]
        idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)
        msg = {
            "_prebatched": True, "btid": 0,
            "image" + TILEIDX_SUFFIX: idx,
            "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16],
            "frameid": np.arange(len(batch)),
        }
        if palette:
            packed, pal, bits = palettize_tiles(tiles, max_colors=256)
            suffix = TILEPAL4_SUFFIX if bits == 4 else TILEPAL8_SUFFIX
            msg["image" + suffix] = packed
            msg["image" + PALETTE_SUFFIX] = pal
        else:
            msg["image" + TILES_SUFFIX] = tiles
        if with_ref:
            msg["image" + TILEREF_SUFFIX] = ref
        return msg

    def messages():
        yield tile_msg(frames[0:8], True, palette=False)
        yield tile_msg(frames[8:16], False, palette=True)

    with StreamDataPipeline(
        messages(), batch_size=8, sharding=sharding, multihost=True
    ) as pipe:
        got = list(pipe)

    assert len(got) == 2
    for start, b in zip((0, 8), got):
        img = np.asarray(b["image"])
        assert img.shape == (8, 32, 32, 4)
        # decoded field is a global array sharded over the data axis
        assert b["image"].sharding.is_equivalent_to(sharding, 4)
        for i in range(8):
            np.testing.assert_array_equal(img[i], frames[start + i])


def test_multihost_tiles_chunked_superbatch():
    """chunk>1 x multihost (single-process SPMD stand-in on the virtual
    8-device mesh): K compatible tile batches assemble into ONE global
    (K, B, ...) superbatch, chunk axis replicated / batch axis sharded,
    decoded bit-exactly in one call (VERDICT r2 item 4; the true
    2-process case is tests/test_multiprocess.py)."""
    from jax.sharding import PartitionSpec as P

    from blendjax.data import StreamDataPipeline
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
    )
    from blendjax.parallel import batch_sharding, create_mesh

    mesh = create_mesh({"data": -1})
    ref, frames = _frames(n=32, shape=(32, 32), seed=12)
    enc = TileDeltaEncoder(ref, tile=16)
    B = 8  # divisible by the virtual 8-device mesh

    def batch_msg(lo, with_ref):
        deltas = [
            tuple(a.copy() for a in enc.encode(f))
            for f in frames[lo: lo + B]
        ]
        idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)
        msg = {
            "_prebatched": True, "btid": 0,
            "image" + TILEIDX_SUFFIX: idx,
            "image" + TILES_SUFFIX: tiles,
            "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16],
            "frameid": np.arange(B) + lo,
        }
        if with_ref:
            msg["image" + TILEREF_SUFFIX] = ref
        return msg

    def messages():
        for n in range(4):  # 2 groups of K=2 batches of 8 frames
            yield batch_msg(B * n, with_ref=n == 0)

    with StreamDataPipeline(
        messages(), batch_size=B, sharding=batch_sharding(mesh),
        multihost=True, chunk=2,
    ) as pipe:
        got = list(pipe)
    assert [np.asarray(b["image"]).shape for b in got] == [
        (2, B, 32, 32, 4)
    ] * 2
    from blendjax.testing.equivalence import normalized_spec

    for b in got:
        # canonicalization-proof layout compare (some jax releases
        # deliver P(None, 'data') as P(None, ('data',)))
        assert normalized_spec(b["image"].sharding) == (None, "data")
        img = np.asarray(b["image"])
        fid = np.asarray(b["frameid"])
        for k in range(2):
            for i in range(B):
                np.testing.assert_array_equal(
                    img[k, i], frames[int(fid[k, i])]
                )

    # Stream end mid-group: the trailing short group flushes as K'=1
    # (the same lockstep rule — every process ends together under SPMD).
    def three_batches():
        for n in range(3):
            yield batch_msg(B * n, with_ref=n == 0)

    with StreamDataPipeline(
        three_batches(), batch_size=B, sharding=batch_sharding(mesh),
        multihost=True, chunk=2,
    ) as pipe:
        tail = list(pipe)
    assert [np.asarray(b["image"]).shape for b in tail] == [
        (2, B, 32, 32, 4), (1, B, 32, 32, 4)
    ]
    short = np.asarray(tail[1]["image"])
    for i in range(B):
        np.testing.assert_array_equal(
            short[0, i], frames[int(np.asarray(tail[1]["frameid"])[0, i])]
        )


@pytest.mark.tpu
def test_pallas_scatter_decode_on_real_tpu():
    """Non-interpret lowering of the scatter kernel on actual hardware
    (run with BLENDJAX_TEST_TPU=1 pytest -m tpu)."""
    ref, frames = _frames(n=4, shape=(64, 64), seed=21)
    enc = TileDeltaEncoder(ref, tile=16)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    out = np.asarray(
        decode_tile_delta(
            jax.device_put(np.asarray(tile_ref(ref, 16))),
            jax.device_put(idx), jax.device_put(tiles),
            ref.shape, use_pallas=True,
        )
    )
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(out[i], f)


@pytest.mark.tpu
def test_spatial_decode_on_real_tpu():
    """Non-interpret lowering of the direct-spatial kernel on actual
    hardware (run with BLENDJAX_TEST_TPU=1 pytest -m tpu)."""
    ref, frames = _frames(n=4, shape=(64, 64), seed=25)
    enc = TileDeltaEncoder(ref, tile=(16, 32))
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles)
    out = np.asarray(
        decode_tile_delta(
            jax.device_put(np.asarray(tile_ref(ref, (16, 32)))),
            jax.device_put(idx), jax.device_put(tiles),
            ref.shape, use_pallas=True,
        )
    )
    for i, f in enumerate(frames):
        np.testing.assert_array_equal(out[i], f)


def test_tile_stream_survives_producer_respawn():
    """Kill a tile-encoding producer mid-stream with respawn=True: the
    respawned process re-sends its reference image (first-message rule),
    so decode state stays correct per (field, btid)."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.producer.sim import CubeScene

    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=1,
        named_sockets=["DATA"],
        seed=7,
        respawn=True,
        instance_args=[
            ["--shape", "64", "64", "--batch", "4", "--encoding", "tile",
             "--tile", "16"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"], batch_size=4,
            # generous timeout: the respawned interpreter needs a few
            # seconds to boot on a loaded core before publishing resumes
            # (3 retries x this budget before the stream gives up)
            launcher=launcher, timeoutms=15000,
        ) as pipe:
            it = iter(pipe)
            first = next(it)
            launcher.processes[0].terminate()
            # Drain queued pre-kill batches (SNDHWM + RCVHWM + kernel TCP
            # buffers hold many of these small messages) until the
            # respawned producer's restarted frame sequence shows up
            # (frameids reset to 1..4); bounded so a broken respawn fails
            # rather than spins.
            after = []
            for _ in range(500):
                b = next(it)
                after.append(b)
                if int(np.asarray(b["frameid"])[0]) == 1:
                    break
            else:
                raise AssertionError("never saw the respawned producer's "
                                     "restarted frame sequence")
    # every frame (pre- and post-respawn) reconstructs bit-exact against
    # a local re-render: the producer is deterministic from seed 7, and
    # the respawned process replays the same sequence from frame 1.
    fmax = max(
        int(f) for b in [first, *after] for f in np.asarray(b["frameid"])
    )
    scene = CubeScene(shape=(64, 64), seed=7)
    local = {}
    for f in range(1, fmax + 1):
        scene.step(f)
        local[f] = scene.render().copy()
    checked = 0
    for b in [first, *after]:
        img = np.asarray(b["image"])
        for i, f in enumerate(np.asarray(b["frameid"])):
            np.testing.assert_array_equal(img[i], local[int(f)])
            checked += 1
    assert checked >= 8  # at least first + the post-respawn batch


def test_np_decoder_handles_non_suffix_sentinels():
    """decode_tile_delta_np pairs indices and tiles positionally (like
    the device decoder), even when sentinels are not a trailing suffix."""
    from blendjax.ops.tiles import decode_tile_delta_np

    ref, frames = _frames(n=1, shape=(32, 32), seed=17)
    img = frames[0]
    enc = TileDeltaEncoder(ref, tile=16)
    fi, ft = enc.encode(img)
    fi, ft = fi.copy(), ft.copy()
    n = enc.num_tiles
    # interleave sentinels before real entries
    idx = np.full((1, len(fi) * 2), n, np.int32)
    tiles = np.zeros((1, len(fi) * 2, 16, 16, 4), np.uint8)
    idx[0, 1::2] = fi
    tiles[0, 1::2] = ft
    out = decode_tile_delta_np(ref, idx, tiles, tile=16)
    np.testing.assert_array_equal(out[0], img)


def test_keyframe_interval_lets_late_consumer_sync():
    """A consumer that missed the initial reference (simulated by a
    stream whose first tile messages carry no ref) skips until a
    keyframe arrives, then decodes exactly — the multi-worker /
    multi-epoch story for tile streams."""
    from blendjax.data import StreamDataPipeline
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
    )

    ref, frames = _frames(n=12, shape=(32, 32), seed=19)
    enc = TileDeltaEncoder(ref, tile=16)

    def messages():
        for start in range(0, 12, 4):
            batch = frames[start:start + 4]
            deltas = [tuple(a.copy() for a in enc.encode(f)) for f in batch]
            idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)
            msg = {
                "_prebatched": True,
                "btid": 0,
                "image" + TILEIDX_SUFFIX: idx,
                "image" + TILES_SUFFIX: tiles,
                "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16],
                "frameid": np.arange(start, start + 4),
            }
            if start == 8:  # ref arrives only in the LAST message
                msg["image" + TILEREF_SUFFIX] = ref
            yield msg

    pipe = StreamDataPipeline(messages(), batch_size=4)
    got = list(pipe)
    # first two batches skipped (no ref yet); the keyframe batch decodes
    assert len(got) == 1
    img = np.asarray(got[0]["image"])
    for i, f in enumerate(np.asarray(got[0]["frameid"])):
        np.testing.assert_array_equal(img[i], frames[int(f)])


def test_torch_adapter_multi_epoch_tile_stream():
    """Epoch 2 over the same dataset instance still decodes: refs persist
    on the instance after the producer's one-time ref message."""
    from blendjax.data.torch_compat import RemoteIterableDataset
    from blendjax.launcher import PythonProducerLauncher

    import os as _os

    producer = _os.path.join(
        _os.path.dirname(__file__), "..", "examples", "datagen",
        "cube_producer.py",
    )
    with PythonProducerLauncher(
        script=producer,
        num_instances=1,
        named_sockets=["DATA"],
        seed=8,
        instance_args=[
            ["--shape", "64", "64", "--batch", "4", "--encoding", "tile",
             "--tile", "16", "--ref-interval", "0"]  # ref sent ONCE
        ],
    ) as launcher:
        ds = RemoteIterableDataset(
            launcher.addresses["DATA"], max_items=8, timeoutms=30_000
        )
        epoch1 = list(ds)
        epoch2 = list(ds)  # fresh iterator; refs persist on the instance
    # max_items=8 counts ITEMS (2 producer batches of 4), per epoch
    assert len(epoch1) == 8 and len(epoch2) == 8
    for it in epoch2:
        assert it["image"].shape == (64, 64, 4)


def test_multi_producer_tile_fan_in_bit_exact():
    """Two tile-encoding producers fan into one consumer: per-(field,
    btid) references keep every interleaved batch decoding against the
    right producer's ref, bit-exact per seed."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.producer.sim import CubeScene

    seed = 31
    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=2,
        named_sockets=["DATA"],
        seed=seed,
        instance_args=[
            ["--shape", "64", "64", "--batch", "4", "--encoding", "tile",
             "--tile", "16"]
        ] * 2,
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"], batch_size=4, timeoutms=30_000
        ) as pipe:
            it = iter(pipe)
            batches = [next(it) for _ in range(8)]
    # launcher seeds instances seed+0, seed+1; re-render both locally
    local = {}
    for inst in (0, 1):
        scene = CubeScene(shape=(64, 64), seed=seed + inst)
        for f in range(1, 80):
            scene.step(f)
            local[(inst, f)] = scene.render().copy()
    seen_btids = set()
    for b in batches:
        btid = int(np.asarray(b["btid"]))
        seen_btids.add(btid)
        img = np.asarray(b["image"])
        for i, f in enumerate(np.asarray(b["frameid"])):
            np.testing.assert_array_equal(img[i], local[(btid, int(f))])
    assert seen_btids == {0, 1}  # fair fan-in actually interleaved


def test_chunked_pipeline_superbatches_bit_exact():
    """chunk=4: the pipeline yields (4, B, H, W, C) superbatches, one
    transfer + one decode per group, still bit-exact per frame."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.producer.sim import CubeScene

    seed = 41
    mesh = Mesh(np.array(jax.devices()).reshape(-1), axis_names=("data",))
    sharding = NamedSharding(mesh, P("data"))
    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=1,
        named_sockets=["DATA"],
        seed=seed,
        instance_args=[
            ["--shape", "64", "64", "--batch", "8", "--encoding", "tile",
             "--tile", "16"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"], batch_size=8, chunk=4,
            sharding=sharding, timeoutms=30_000,
        ) as pipe:
            it = iter(pipe)
            supers = [next(it) for _ in range(2)]
    scene = CubeScene(shape=(64, 64), seed=seed)
    local = {}
    for f in range(1, 128):
        scene.step(f)
        local[f] = scene.render().copy()
    for sb in supers:
        assert sb["image"].shape == (4, 8, 64, 64, 4)
        assert sb["frameid"].shape == (4, 8)
        # chunk axis replicated, batch axis sharded over the mesh
        assert sb["image"].sharding.spec == P(None, "data")
        img = np.asarray(sb["image"])
        fid = np.asarray(sb["frameid"])
        for k in range(4):
            for i in range(8):
                np.testing.assert_array_equal(
                    img[k, i], local[int(fid[k, i])]
                )


def test_chunked_step_equals_sequential_steps():
    """One jitted scan over a (K, B, ...) superbatch produces the same
    final params as K sequential per-batch steps (SGD)."""
    import optax

    from blendjax.models import CubeRegressor
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import (
        make_chunked_supervised_step,
        make_supervised_step,
        make_train_state,
    )

    mesh = create_mesh({"data": -1})
    sh = batch_sharding(mesh)
    rng = np.random.default_rng(3)
    K, B = 3, 4
    images = rng.integers(0, 255, (K, B, 32, 32, 4), np.uint8)
    xys = (rng.random((K, B, 8, 2)) * 32).astype(np.float32)
    s0 = make_train_state(
        CubeRegressor(), images[0], mesh=mesh, optimizer=optax.sgd(0.01)
    )
    seq = make_supervised_step(mesh=mesh, batch_sharding=sh, donate=False)
    chunked = make_chunked_supervised_step(donate=False)

    s_seq = s0
    seq_losses = []
    for k in range(K):
        s_seq, m = seq(s_seq, {"image": images[k], "xy": xys[k]})
        seq_losses.append(float(m["loss"]))
    s_chk, mc = chunked(s0, {"image": images, "xy": xys})
    np.testing.assert_allclose(
        np.asarray(mc["loss"]), seq_losses, rtol=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        s_seq.params, s_chk.params,
    )


def test_fused_tile_step_matches_decode_then_step():
    """emit_packed + make_fused_tile_step trains bit-identically to the
    decode-then-chunked-step pipeline over the same synthetic tile
    stream (same SGD trajectory, same losses)."""
    import optax

    from blendjax.data import StreamDataPipeline
    from blendjax.models import CubeRegressor
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
    )
    from blendjax.train import (
        make_chunked_supervised_step,
        make_fused_tile_step,
        make_train_state,
    )

    ref, frames = _frames(n=8, shape=(32, 32), seed=11)
    rng = np.random.default_rng(5)
    xys = (rng.random((4, 2, 8, 2)) * 32).astype(np.float32)
    enc = TileDeltaEncoder(ref, tile=16)

    def messages():
        for g in range(4):  # 4 batches of 2 frames
            batch = frames[2 * g: 2 * g + 2]
            deltas = [tuple(a.copy() for a in enc.encode(f)) for f in batch]
            idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)
            msg = {
                "_prebatched": True, "btid": 0,
                "image" + TILEIDX_SUFFIX: idx,
                "image" + TILES_SUFFIX: tiles,
                "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16],
                "xy": xys[g],
            }
            if g == 0:
                msg["image" + TILEREF_SUFFIX] = ref
            yield msg

    s0 = make_train_state(
        CubeRegressor(), frames[0][None].repeat(2, 0),
        optimizer=optax.sgd(0.01),
    )

    with StreamDataPipeline(messages(), batch_size=2, chunk=2) as pipe:
        decoded = list(pipe)
    assert [np.asarray(b["image"]).shape for b in decoded] == [
        (2, 2, 32, 32, 4)
    ] * 2
    chunked = make_chunked_supervised_step(donate=False)
    s_ref = s0
    ref_losses = []
    for b in decoded:
        s_ref, m = chunked(s_ref, {"image": b["image"], "xy": b["xy"]})
        ref_losses.extend(np.asarray(m["loss"]).tolist())

    with StreamDataPipeline(
        messages(), batch_size=2, chunk=2, emit_packed=True
    ) as pipe:
        packed_batches = list(pipe)
    assert all("_packed" in b for b in packed_batches)
    fused = make_fused_tile_step(donate=False)
    s_fused = s0
    fused_losses = []
    for b in packed_batches:
        s_fused, m = fused(s_fused, b)
        fused_losses.extend(np.asarray(m["loss"]).tolist())

    np.testing.assert_allclose(fused_losses, ref_losses, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-8
        ),
        s_ref.params, s_fused.params,
    )


def test_palettize_roundtrip_and_fallbacks():
    """Palette compression: 4-bit for <=16 colors, 8-bit for <=256, None
    beyond; native and numpy passes agree; expansion is bit-exact."""
    from blendjax.ops.tiles import (
        expand_palette_tiles_np,
        palettize_tiles,
    )

    rng = np.random.default_rng(23)

    def tiles_with_colors(ncolors):
        pal = rng.integers(0, 255, (ncolors, 4), np.uint8)
        idx = rng.integers(0, ncolors, (2, 5, 16, 16))
        return pal[idx]

    t12 = tiles_with_colors(12)
    packed, pal, bits = palettize_tiles(t12)
    assert bits == 4 and packed.shape == (2, 5, 128) and pal.shape == (16, 4)
    np.testing.assert_array_equal(
        expand_palette_tiles_np(packed, pal, 4, 16, 4), t12
    )

    t100 = tiles_with_colors(100)
    packed, pal, bits = palettize_tiles(t100)
    assert bits == 8 and packed.shape == (2, 5, 256) and pal.shape == (256, 4)
    np.testing.assert_array_equal(
        expand_palette_tiles_np(packed, pal, 8, 16, 4), t100
    )

    # >256 colors: every pixel unique in one tile region
    many = np.arange(2 * 5 * 16 * 16 * 4, dtype=np.uint32)
    many = (many % 251 * 7919 + many).astype(np.uint32)
    tmany = many.view(np.uint8)[: 2 * 5 * 16 * 16 * 4].reshape(2, 5, 16, 16, 4)
    assert palettize_tiles(tmany) is None

    # numpy fallback agrees with native
    from blendjax._native import load_palettize

    if load_palettize() is not None:
        import os as _os

        native_res = palettize_tiles(t12)
        _os.environ["BLENDJAX_NO_NATIVE"] = "1"
        try:
            # the loader caches; emulate numpy path by calling internals
            from blendjax._native import build as _b

            _b._CACHE.pop("palettize", None)
            numpy_res = palettize_tiles(t12)
        finally:
            del _os.environ["BLENDJAX_NO_NATIVE"]
            _b._CACHE.pop("palettize", None)
        np.testing.assert_array_equal(
            expand_palette_tiles_np(*native_res[:2], native_res[2], 16, 4),
            expand_palette_tiles_np(*numpy_res[:2], numpy_res[2], 16, 4),
        )


def test_chunk_strict_rejects_raw_messages():
    """chunk>1 with chunk_strict=True over a stream containing a non-tile
    message fails loudly (opt-in fail-fast contract)."""
    from blendjax.data import StreamDataPipeline

    def messages():
        yield {"_batched": True, "btid": 0,
               "image": np.zeros((4, 32, 32, 4), np.uint8)}

    pipe = StreamDataPipeline(
        messages(), batch_size=4, chunk=4, chunk_strict=True
    )
    with pytest.raises(RuntimeError, match="all-tile"):
        list(pipe)


def test_chunk_mode_degrades_on_mixed_stream(caplog):
    """Default chunk>1 behavior on a mixed stream: the in-flight tile
    group flushes, the raw batch passes through as a K'=1 superbatch with
    one warning, and every frame still reconstructs bit-exactly."""
    import logging

    from blendjax.data import StreamDataPipeline
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
    )

    ref, frames = _frames(n=8, shape=(32, 32), seed=4)
    enc = TileDeltaEncoder(ref, tile=16)
    raw = np.stack(frames[4:6])  # the misconfigured producer's batch

    def tile_msg(batch, with_ref):
        deltas = [tuple(a.copy() for a in enc.encode(f)) for f in batch]
        idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)
        msg = {
            "_prebatched": True, "btid": 0,
            "image" + TILEIDX_SUFFIX: idx,
            "image" + TILES_SUFFIX: tiles,
            "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16],
        }
        if with_ref:
            msg["image" + TILEREF_SUFFIX] = ref
        return msg

    def messages():
        yield tile_msg(frames[0:2], True)   # group member 1
        yield {"_batched": True, "btid": 1, "image": raw}  # intruder
        yield tile_msg(frames[2:4], False)  # group member after flush
        yield tile_msg(frames[6:8], False)

    from blendjax.utils.metrics import metrics

    degraded0 = metrics.counters.get("tiles.degraded_groups", 0)
    with caplog.at_level(logging.WARNING, logger="blendjax.data"):
        pipe = StreamDataPipeline(messages(), batch_size=2, chunk=2)
        got = list(pipe)

    # flushed group of 1, the K'=1 raw superbatch, then a full group of 2
    shapes = [np.asarray(b["image"]).shape for b in got]
    assert shapes == [
        (1, 2, 32, 32, 4), (1, 2, 32, 32, 4), (2, 2, 32, 32, 4)
    ]
    np.testing.assert_array_equal(np.asarray(got[0]["image"])[0, 0], frames[0])
    np.testing.assert_array_equal(np.asarray(got[0]["image"])[0, 1], frames[1])
    np.testing.assert_array_equal(np.asarray(got[1]["image"])[0], raw)
    np.testing.assert_array_equal(np.asarray(got[2]["image"])[0, 0], frames[2])
    np.testing.assert_array_equal(np.asarray(got[2]["image"])[1, 1], frames[7])
    warns = [r for r in caplog.records if "non-tile message" in r.message]
    assert len(warns) == 1
    # the degradation is countable, not just logged (fleet visibility)
    assert (
        metrics.counters.get("tiles.degraded_groups", 0) - degraded0 == 1
    )


def test_prebatched_size_mismatch_warns_once(caplog):
    """A producer batch size differing from the pipeline's passes through
    ragged, flagged by a single warning."""
    import logging

    from blendjax.data import StreamDataPipeline
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
    )

    ref, frames = _frames(n=6, shape=(32, 32), seed=2)
    enc = TileDeltaEncoder(ref, tile=16)

    def messages():
        for start in (0, 3):
            batch = frames[start:start + 3]  # producer batches of 3
            deltas = [tuple(a.copy() for a in enc.encode(f)) for f in batch]
            idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)
            msg = {
                "_prebatched": True, "btid": 0,
                "image" + TILEIDX_SUFFIX: idx,
                "image" + TILES_SUFFIX: tiles,
                "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16],
            }
            if start == 0:
                msg["image" + TILEREF_SUFFIX] = ref
            yield msg

    with caplog.at_level(logging.WARNING, logger="blendjax.data"):
        pipe = StreamDataPipeline(messages(), batch_size=8)  # != 3
        got = list(pipe)
    assert [b["image"].shape[0] for b in got] == [3, 3]  # ragged pass-through
    for start, b in zip((0, 3), got):
        img = np.asarray(b["image"])
        for i in range(3):
            np.testing.assert_array_equal(img[i], frames[start + i])
    warns = [r for r in caplog.records if "prebatched" in r.message]
    assert len(warns) == 1  # warned once, not per message


# -- full-frame palette codec (the non-sparse path) --------------------------


def test_palettize_frames_roundtrip_all_widths_and_overflow():
    """Per-frame full-frame palettes: the widest FRAME picks 2/4/8-bit
    indices; every width round-trips bit-exact (numpy and device twins),
    and a single >256-color frame fails the whole batch to raw."""
    from blendjax.ops.tiles import (
        expand_palette_frames,
        expand_palette_frames_np,
        palettize_frames,
    )

    rng = np.random.default_rng(0)
    h, w = 16, 24

    def roundtrip(frames, want_bits, want_len):
        packed, pal, bits = palettize_frames(frames)
        assert bits == want_bits and packed.shape == (len(frames), want_len)
        assert pal.ndim == 3 and pal.shape[0] == len(frames)  # per-frame
        np.testing.assert_array_equal(
            expand_palette_frames_np(packed, pal, bits, h, w, 4), frames
        )
        np.testing.assert_array_equal(
            np.asarray(jax.jit(
                lambda p, q: expand_palette_frames(p, q, bits, h, w, 4)
            )(packed, pal)),
            frames,
        )

    # <=4 colors per frame -> 2-bit (16x)
    tiny = np.repeat(
        rng.integers(0, 4, (4, h, w, 1), np.uint8) * 60, 4, axis=-1
    )
    roundtrip(tiny, 2, h * w // 4)
    # <=16 colors per frame -> 4-bit (8x); per-frame tables mean DISTINCT
    # colors across frames still fit (here ~64 batch-wide)
    few = np.stack([
        np.repeat(
            rng.integers(0, 16, (h, w, 1), np.uint8) * 13 + i * 17,
            4, axis=-1,
        )
        for i in range(4)
    ])
    roundtrip(few, 4, h * w // 2)
    # <=256 colors in one frame -> 8-bit (4x)
    some = np.repeat(
        rng.integers(0, 200, (4, h, w, 1), np.uint8), 4, axis=-1
    )
    roundtrip(some, 8, h * w)
    # >256 colors in any frame -> None (ship raw)
    many = rng.integers(0, 255, (2, 32, 32, 4), np.uint8)
    assert palettize_frames(many) is None


def _palette_case(bits, form, rank):
    """``(expand, expand_np, packed, palette)`` of one expansion: every
    byte value under every palette, palettes with unused (zero) rows as
    the codec pads them, and the values at the ends and the middle."""
    from blendjax.ops import tiles as T

    rng = np.random.default_rng(0)
    lead = {"shared": (3,), "per_row": (3,), "per_row_vmap2": (2, 3)}[rank]
    cap = 1 << bits
    if form == "tiles":
        th, tw, c = 16, 32, 4
        body = (2, th * tw * bits // 8)  # K tiles of M packed bytes
        expand = lambda p, q: T.expand_palette_tiles(p, q, bits, (th, tw), c)
        expand_np = lambda p, q: T.expand_palette_tiles_np(
            p, q, bits, (th, tw), c
        )
    else:
        h, w, c = 32, 32, 3  # 3 channels: a row of 384 lanes, not 128
        body = (h * w * bits // 8,)
        expand = lambda p, q: T.expand_palette_frames(p, q, bits, h, w, c)
        expand_np = lambda p, q: T.expand_palette_frames_np(
            p, q, bits, h, w, c
        )
    packed = rng.integers(0, 256, (*lead, *body), np.uint8)
    packed.reshape(*lead, -1)[..., :256] = np.arange(256, dtype=np.uint8)
    palette = rng.integers(
        0, 256, (*(() if rank == "shared" else lead), cap, c), np.uint8
    )
    palette[..., 0, :] = (0, 1, 127, 128)[:c]
    palette[..., 1, :] = (255, 254, 129, 0)[:c]
    palette[..., cap - cap // 4:, :] = 0  # the codec's zero padding
    return expand, expand_np, packed, palette


@pytest.mark.parametrize("rank", ["shared", "per_row", "per_row_vmap2"])
@pytest.mark.parametrize("form", ["tiles", "frames"])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_palette_expand_is_bit_exact(bits, form, rank):
    """The device expansion against its numpy twin, byte for byte: the
    select form of 2 and 4 bits and the gather of 8, with one palette a
    batch, one a row (one ``vmap``) and one a row of a chunk group (the
    fused step's ``vmap(vmap(palette_expand))``)."""
    expand, expand_np, packed, palette = _palette_case(bits, form, rank)
    got = jax.jit(expand)(packed, palette)
    want = expand_np(packed, palette)
    assert got.dtype == np.uint8 and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), want)


def _primitives(jaxpr) -> set:
    """Names of the primitives of ``jaxpr`` and of every jaxpr nested
    in its equations' parameters."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("form", ["tiles", "frames"])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_palette_expand_gathers_only_at_8_bits(bits, form):
    """A TPU looks a gather's indices up one at a time (the byte table of
    2- and 4-bit indices was the step's largest operation), so the table
    look-up must not come back unnoticed; 8-bit palettes keep theirs,
    which also shows that this test would see one."""
    expand, _, packed, palette = _palette_case(bits, form, "per_row_vmap2")
    prims = _primitives(jax.make_jaxpr(expand)(packed, palette).jaxpr)
    assert ("gather" in prims) == (bits == 8), sorted(prims)


def test_stream_pipeline_pal_encoding_end_to_end():
    """--encoding pal -> ONE packed transfer per batch, decoded by a
    device gather to bit-exact full frames (the lossless non-sparse
    codec; VERDICT r3 next #2)."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.producer.sim import CubeScene
    from blendjax.utils.metrics import metrics as reg

    mesh = Mesh(np.array(jax.devices()).reshape(-1), axis_names=("data",))
    sharding = NamedSharding(mesh, P("data"))
    seed = 7
    reg.reset()
    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=1,
        named_sockets=["DATA"],
        seed=seed,
        instance_args=[
            ["--shape", "64", "64", "--batch", "8", "--encoding", "pal"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"],
            batch_size=8,
            sharding=sharding,
            timeoutms=30_000,
        ) as pipe:
            it = iter(pipe)
            batches = [next(it) for _ in range(3)]

    scene = CubeScene(shape=(64, 64), seed=seed)
    local = {}
    for f in range(1, 8 * len(batches) + 1):
        scene.step(f)
        local[f] = scene.render().copy()

    for b in batches:
        assert b["image"].shape == (8, 64, 64, 4)
        assert b["image"].dtype == np.uint8
        img = np.asarray(b["image"])
        for i, f in enumerate(np.asarray(b["frameid"])):
            np.testing.assert_array_equal(img[i], local[int(f)])
    # wire accounting: the codec actually compressed (cube scene fits
    # pal4 => ~8x; assert a conservative 3x)
    wire = reg.counters.get("pal.wire_bytes", 0)
    decoded = reg.counters.get("pal.decoded_bytes", 0)
    assert decoded and wire and decoded / wire > 3.0


def test_pal_stream_chunk_mode_superbatch_bit_exact():
    """chunk>1 coalesces K packed pal batches into ONE stacked transfer
    decoded to a (K, B, ...) superbatch — bit-exact per frame, each
    group member through its own palette (the non-sparse row's
    op-latency fix: K transfers + K dispatches collapse K-fold)."""
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.producer.sim import CubeScene

    mesh = Mesh(np.array(jax.devices()).reshape(-1), axis_names=("data",))
    sharding = NamedSharding(mesh, P("data"))
    seed = 3
    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=1,
        named_sockets=["DATA"],
        seed=seed,
        instance_args=[
            ["--shape", "64", "64", "--batch", "8", "--encoding", "pal"]
        ],
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"],
            batch_size=8,
            sharding=sharding,
            chunk=2,
            timeoutms=30_000,
        ) as pipe:
            it = iter(pipe)
            sb = next(it)
    assert sb["image"].shape == (2, 8, 64, 64, 4)  # (K, B, ...)
    scene = CubeScene(shape=(64, 64), seed=seed)
    local = {}
    for f in range(1, 17):
        scene.step(f)
        local[f] = scene.render().copy()
    img = np.asarray(sb["image"]).reshape(16, 64, 64, 4)
    for i, f in enumerate(np.asarray(sb["frameid"]).reshape(-1)):
        np.testing.assert_array_equal(img[i], local[int(f)])


def test_pal_stream_multihost_host_expand_fallback():
    """Full-frame palette batches in a multihost pipeline stay CORRECT
    via the host-expand fallback: frames decode on the host and ride
    the standard global-assembly path, bit-exact."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from blendjax.data import StreamDataPipeline
    from blendjax.ops.tiles import (
        FRAMEPAL8_SUFFIX,
        FRAMESHAPE_SUFFIX,
        PALETTE_SUFFIX,
        palettize_frames,
    )
    from blendjax.parallel import batch_sharding, create_mesh

    n = len(jax.devices())
    mesh = create_mesh({"data": -1})
    rng = np.random.default_rng(5)
    frames = np.repeat(
        rng.integers(0, 40, (n, 16, 24, 1), np.uint8) * 6, 4, axis=-1
    )
    out = palettize_frames(frames)
    assert out is not None
    packed, pal, bits = out
    from blendjax.ops.tiles import FRAMEPAL_SUFFIXES

    suffix = FRAMEPAL_SUFFIXES[bits]
    msg = {
        "_prebatched": True, "btid": 0,
        "image" + suffix: packed,
        "xy": np.zeros((n, 8, 2), np.float32),
        "image" + PALETTE_SUFFIX: pal,
        "image" + FRAMESHAPE_SUFFIX: np.array([16, 24, 4, bits], np.int32),
    }
    with StreamDataPipeline(
        iter([msg]), batch_size=n, sharding=batch_sharding(mesh),
        multihost=True,
    ) as pipe:
        (b,) = list(pipe)
    assert b["image"].shape == (n, 16, 24, 4)
    np.testing.assert_array_equal(np.asarray(b["image"]), frames)


# -- run-length ("ndr") tile-group codec -------------------------------------


def test_rle_encode_expand_roundtrip_device_equals_host():
    """rle_expand_packed (the in-jit scan/gather) and the numpy twin
    reconstruct bit-exactly, for pixel runs (isz=4) and byte runs
    (isz=1) including runs past the uint16 split point."""
    import jax

    from blendjax.ops.tiles import (
        rle_encode_rows,
        rle_expand_packed,
        rle_expand_packed_np,
    )

    rng = np.random.default_rng(0)
    img = np.zeros((4, 48, 48, 4), np.uint8)
    img[:, 8:20, 4:40] = rng.integers(0, 5, (4, 12, 36, 4), dtype=np.uint8)
    flat = np.zeros((2, 70_000), np.uint8)
    flat[1, 500:700] = 9  # one >65535 background run split at encode
    for arr in (img, flat):
        buf, cap, isz = rle_encode_rows(arr)
        host = rle_expand_packed_np(buf, arr.shape, isz, cap)
        np.testing.assert_array_equal(host, arr)
        dev = jax.jit(
            rle_expand_packed, static_argnums=(1, 2, 3)
        )(buf, arr.shape, isz, cap)
        np.testing.assert_array_equal(np.asarray(dev), arr)


def test_rle_validation_guards_device_plan():
    from blendjax.ops.tiles import (
        rle_encode_rows,
        rle_validate_packed,
    )

    img = np.zeros((4, 32, 32, 4), np.uint8)
    img[:, 4:12, 4:12] = 3
    buf, cap, isz = rle_encode_rows(img)
    rle_validate_packed(buf, img.shape, isz, cap)  # honest buffer passes
    with pytest.raises(ValueError, match="does not match"):
        rle_validate_packed(buf[:, :-4], img.shape, isz, cap)
    bad = buf.copy()
    bad[:, cap * isz:] = 0  # wipe the run planes: rows under-declare
    with pytest.raises(ValueError, match="declared"):
        rle_validate_packed(bad, img.shape, isz, cap)
    with pytest.raises(ValueError, match="out of bounds"):
        rle_validate_packed(buf, img.shape, isz, 0)


def test_decode_packed_pal_batch_expands_rle_groups():
    """The shared decode entry point expands deferred run buffers
    FIRST, so a run-packed raw frame (empty pal_groups) and a
    run-packed palette plane both restore inside one jit."""
    import jax

    from blendjax.ops.tiles import (
        NDR_SUFFIX,
        decode_packed_pal_batch,
        pack_fields,
        rle_encode_rows,
    )

    img = np.zeros((4, 32, 32, 4), np.uint8)
    img[:, 10:20, 10:20] = 6
    xy = np.arange(4 * 8 * 2, dtype=np.float32).reshape(4, 8, 2)
    buf, cap, isz = rle_encode_rows(img)
    packed, spec = pack_fields({"image" + NDR_SUFFIX: buf, "xy": xy})
    rle_groups = (("image", (img.shape, isz, cap)),)
    out = jax.jit(
        decode_packed_pal_batch,
        static_argnames=("spec", "pal_groups", "rle_groups"),
    )(packed, spec=spec, pal_groups=(), rle_groups=rle_groups)
    np.testing.assert_array_equal(np.asarray(out["image"]), img)
    np.testing.assert_array_equal(np.asarray(out["xy"]), xy)
