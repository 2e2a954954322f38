"""Multi-process JAX worker for tests/test_multiprocess.py.

Runs as one of N coordinated processes (``jax.distributed.initialize``
over a localhost coordinator, 4 virtual CPU devices per process — the
CPU stand-in for one TPU host of a multi-host pod, SURVEY.md §4
"multi-process CPU JAX tests mirroring the reference's mp.Process
trick"). Asserts, from every process:

- DeviceFeeder(multihost=True) assembles per-process local batches into
  ONE global array of the right shape, content, and sharding;
- a psum collective over the assembled batch sees every process's rows;
- a tile-delta stream decodes through the multihost pipeline path with
  each process's local shard rows bit-exact vs its own frames;
- chunk=4 tile streams flush in lockstep into ONE global (K, B, ...)
  superbatch per group, bit-exact per shard (VERDICT r2 item 4);
- mode "divergent-ref": processes send DIFFERENT reference content and
  the fleet-digest all-gather must fail loudly on every process
  (ADVICE r2 medium).

Usage: mp_worker.py PROCESS_ID NUM_PROCESSES COORD_PORT [MODE]
(env JAX_PLATFORMS/XLA_FLAGS are set by the parent test).
"""

import sys

import numpy as np


def main() -> int:
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    mode = sys.argv[4] if len(sys.argv) > 4 else "full"
    import jax

    # before the first backend query, whatever the environment says
    # (same as tests/conftest.py)
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"localhost:{port}", num_processes=nproc, process_id=pid
    )
    assert jax.process_count() == nproc
    local = jax.local_device_count()
    ndev = jax.device_count()
    assert ndev == local * nproc

    from jax.sharding import NamedSharding, PartitionSpec as P

    from blendjax.data.pipeline import DeviceFeeder, StreamDataPipeline
    from blendjax.parallel import create_mesh

    mesh = create_mesh({"data": -1})
    sharding = NamedSharding(mesh, P("data"))

    # -- raw multihost assembly ------------------------------------------
    b_local = local  # one row per local device
    rows = pid * b_local + np.arange(b_local)
    batch = {
        "image": (rows[:, None, None] * np.ones((1, 2, 2))).astype(np.uint8),
        "frameid": rows,
    }
    feeder = DeviceFeeder(sharding=sharding, multihost=True)
    (out,) = list(feeder([batch]))
    assert out["image"].shape == (ndev, 2, 2), out["image"].shape
    assert out["image"].sharding.is_equivalent_to(sharding, 3)
    # every process holds its own rows, in global order
    for shard in out["image"].addressable_shards:
        row = int(np.asarray(shard.data)[0, 0, 0])
        assert row == (shard.index[0].start or 0), (row, shard.index)

    # -- a collective sees all rows --------------------------------------
    total = jax.jit(
        lambda x: jax.numpy.sum(x.astype(jax.numpy.int32)),
        out_shardings=NamedSharding(mesh, P()),
    )(out["frameid"])
    # replicated output: fully addressable on every process
    got = int(np.asarray(total.addressable_shards[0].data))
    assert got == sum(range(ndev)), got

    # -- tile stream through the multihost pipeline path ------------------
    from blendjax.ops.tiles import (
        TILEIDX_SUFFIX,
        TILEREF_SUFFIX,
        TILES_SUFFIX,
        TILESHAPE_SUFFIX,
        TileDeltaEncoder,
        pack_batch,
    )

    if mode == "divergent-ref":
        # Each process ships DIFFERENT reference content: the pipeline's
        # fleet-digest all-gather must raise on every process instead of
        # silently decoding rows against the wrong background.
        bad_ref = np.full((32, 32, 4), 10 + pid, np.uint8)
        enc = TileDeltaEncoder(bad_ref, tile=16)
        deltas = [tuple(a.copy() for a in enc.encode(bad_ref))]
        idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)

        def bad_messages():
            yield {
                "_prebatched": True, "btid": pid,
                "image" + TILEIDX_SUFFIX: idx,
                "image" + TILES_SUFFIX: tiles,
                "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16],
                "image" + TILEREF_SUFFIX: bad_ref,
            }

        try:
            with StreamDataPipeline(
                bad_messages(), batch_size=1, sharding=sharding,
                multihost=True,
            ) as pipe:
                list(pipe)
        except RuntimeError as e:
            assert "DIFFERENT fleet references" in str(e), e
            print(f"mp_worker {pid}/{nproc} divergence-detected")
            return 0
        print(f"mp_worker {pid}/{nproc} ERROR: divergence NOT detected")
        return 1

    rng = np.random.default_rng(7)  # SAME ref content on every process
    ref = rng.integers(0, 255, (32, 32, 4), np.uint8)
    # Rectangular (16, 32) tiles: the 5-element wire form and rect grid
    # math also hold through the true multi-process global-assembly path.
    enc = TileDeltaEncoder(ref, tile=(16, 32))
    frames = []
    for i in range(ndev):
        img = ref.copy()
        img[8:16, 8:16] = (i * 29) % 251
        frames.append(img)
    local_frames = frames[pid * b_local: (pid + 1) * b_local]
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in local_frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=4)

    def messages():
        yield {
            "_prebatched": True, "btid": pid,
            "image" + TILEIDX_SUFFIX: idx,
            "image" + TILES_SUFFIX: tiles,
            "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16, 32],
            "image" + TILEREF_SUFFIX: ref,
            "frameid": np.asarray(rows),
        }

    with StreamDataPipeline(
        messages(), batch_size=b_local, sharding=sharding, multihost=True
    ) as pipe:
        (got_batch,) = list(pipe)
    img = got_batch["image"]
    assert img.shape == (ndev, 32, 32, 4), img.shape
    for shard in img.addressable_shards:
        g = shard.index[0].start or 0
        np.testing.assert_array_equal(np.asarray(shard.data)[0], frames[g])

    # -- chunk>1 tile stream: lockstep flush into (K, B, ...) -------------
    K = 4
    chunk_frames = []  # [k][global row] -> frame
    for k in range(K):
        row = []
        for g in range(ndev):
            img_ = ref.copy()
            img_[0:16, 16:32] = (17 + 31 * g + 7 * k) % 251
            row.append(img_)
        chunk_frames.append(row)

    def chunk_messages():
        for k in range(K):
            local = chunk_frames[k][pid * b_local: (pid + 1) * b_local]
            deltas = [
                tuple(a.copy() for a in enc.encode(f)) for f in local
            ]
            idx_, tiles_ = pack_batch(deltas, enc.num_tiles, capacity=4)
            msg = {
                "_prebatched": True, "btid": pid,
                "image" + TILEIDX_SUFFIX: idx_,
                "image" + TILES_SUFFIX: tiles_,
                "image" + TILESHAPE_SUFFIX: [32, 32, 4, 16, 32],
                "frameid": np.asarray(rows) + 100 * k,
            }
            if k == 0:
                msg["image" + TILEREF_SUFFIX] = ref
            yield msg

    with StreamDataPipeline(
        chunk_messages(), batch_size=b_local, sharding=sharding,
        multihost=True, chunk=K,
    ) as pipe:
        (sb,) = list(pipe)
    assert sb["image"].shape == (K, ndev, 32, 32, 4), sb["image"].shape
    assert sb["frameid"].shape == (K, ndev)
    # chunk axis replicated, batch axis sharded: every process holds its
    # own rows for ALL K updates of the scanned step
    for shard in sb["image"].addressable_shards:
        ks = shard.index[0]
        assert (ks.start or 0) == 0 and (
            ks.stop is None or ks.stop == K
        ), shard.index
        g = shard.index[1].start or 0
        data = np.asarray(shard.data)
        for k in range(K):
            np.testing.assert_array_equal(data[k, 0], chunk_frames[k][g])
    fid = np.asarray(
        jax.jit(
            lambda x: x, out_shardings=NamedSharding(mesh, P())
        )(sb["frameid"]).addressable_shards[0].data
    )
    np.testing.assert_array_equal(
        fid, np.arange(ndev)[None, :] + 100 * np.arange(K)[:, None]
    )

    # -- full-frame palette stream (non-sparse codec) ---------------------
    # multihost pal batches take the host-expand fallback, then the
    # standard global assembly; every process's shard rows must decode
    # bit-exact vs its own frames.
    from blendjax.ops.tiles import (
        FRAMEPAL_SUFFIXES,
        FRAMESHAPE_SUFFIX,
        PALETTE_SUFFIX,
        palettize_frames,
    )

    pal_frames = np.stack([
        np.repeat(
            ((np.arange(32 * 32).reshape(32, 32, 1) + g * 7) % 4
             ).astype(np.uint8) * 61,
            4, axis=-1,
        )
        for g in range(ndev)
    ])
    local_pal = pal_frames[pid * b_local: (pid + 1) * b_local]
    packed, palette, bits = palettize_frames(local_pal)

    def pal_messages():
        yield {
            "_prebatched": True, "btid": pid,
            "image" + FRAMEPAL_SUFFIXES[bits]: packed,
            "frameid": np.asarray(rows),
            "image" + PALETTE_SUFFIX: palette,
            "image" + FRAMESHAPE_SUFFIX: np.array(
                [32, 32, 4, bits], np.int32
            ),
        }

    with StreamDataPipeline(
        pal_messages(), batch_size=b_local, sharding=sharding,
        multihost=True,
    ) as pipe:
        (pb,) = list(pipe)
    assert pb["image"].shape == (ndev, 32, 32, 4), pb["image"].shape
    for shard in pb["image"].addressable_shards:
        g = shard.index[0].start or 0
        np.testing.assert_array_equal(
            np.asarray(shard.data)[0], pal_frames[g]
        )

    print(f"mp_worker {pid}/{nproc} ok: ndev={ndev}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
