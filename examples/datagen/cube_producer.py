"""Producer script: streams rotating-cube images + corner annotations.

The headless counterpart of the reference's ``examples/datagen/
cube.blend.py:6-39`` (randomize in pre_frame, publish in post_frame) and
the producer ``chip_smoke.py`` streams from. Launch it with
:class:`blendjax.launcher.PythonProducerLauncher`; it reads the handshake
(btid/seed/sockets) exactly like a Blender scene script would.

Usage flags (passed via ``instance_args``):
  --shape H W      image size (default 480 640)
  --frames N       stop after N frames (default: run forever)
  --batch B        publish one (B, H, W, 4) message per B frames instead of
                   B per-frame messages (renders straight into the batch
                   buffer; the consumer's ingest passes full batches
                   through without re-assembly)
  --encoding E     'raw' (default) ships full frames; 'tile' ships only
                   the 32x32 tiles that changed vs the scene background
                   (lossless; decoded on-device by the consumer — see
                   blendjax.ops.tiles). Requires --batch > 1.
  --tile T [TW]    tile dims for --encoding tile (default 16 32); two
                   values give rectangular (rows, cols) tiles — (16, 32)
                   at C=4 unlocks the consumer's direct-spatial decode
  --tile-capacity N, --tile-pal-bits {2,4,8}
                   pin the tile stream's wire shape (changed-tile slots
                   per frame, palette index width) across a fleet
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from blendjax.transport import term_context
from blendjax.producer import AnimationController, DataPublisher, parse_launch_args
from blendjax.producer.sim import CubeScene, SimEngine


def main() -> None:
    args, remainder = parse_launch_args(sys.argv)
    parser = argparse.ArgumentParser()
    parser.add_argument("--shape", nargs=2, type=int, default=[480, 640])
    parser.add_argument("--frames", type=int, default=-1)
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument(
        "--encoding", choices=["raw", "tile", "pal"], default="raw"
    )
    # one value = square tiles; two = (rows, cols). Default (16, 32):
    # finer granularity than 32x32 (fewer wasted pixels per changed
    # tile) and, at C=4, rows span 128 lanes — the consumer's
    # direct-spatial Pallas decode engages (docs/performance.md).
    parser.add_argument("--tile", nargs="+", type=int, default=[16, 32])
    parser.add_argument(
        "--tile-rgba", action="store_true",
        help="ship full RGBA tiles (Pallas-decodable) even when alpha is "
        "static, instead of slicing to RGB",
    )
    parser.add_argument(
        "--ref-interval", type=int, default=64,
        help="re-send the tile reference every N batches (keyframes; lets "
        "multiple consumers/workers join a stream). 0 = send once.",
    )
    parser.add_argument(
        "--tile-capacity", type=int, default=0,
        help="pin the per-frame changed-tile capacity (stable shapes "
        "across a producer fleet => one consumer decode compilation and "
        "unbroken chunk groups). 0 = per-stream high-water mark.",
    )
    parser.add_argument(
        "--tile-pal-bits", type=int, choices=[2, 4, 8], default=2,
        help="narrowest palette index width the tile stream ships (it "
        "grows, and stays grown, when a frame needs more colors). Like "
        "--tile-capacity it pins one wire shape across a fleet: about "
        "one cube frame in 200 holds a fifth color, so 4 keeps every "
        "batch the same shape.",
    )
    parser.add_argument(
        "--trace-every", type=int, default=64,
        help="stamp every Nth published message with a sampled "
        "distributed-trace context (blendjax.obs.trace; "
        "docs/observability.md 'Tracing a frame'). 0 disables.",
    )
    opts = parser.parse_args(remainder)

    scene = CubeScene(shape=tuple(opts.shape), seed=args.btseed)
    ctrl = AnimationController(SimEngine(scene))
    flush = None

    if opts.encoding == "tile":
        # Sparse streaming: per frame, render into a reused framebuffer,
        # scan for tiles that differ from the background, and ship only
        # those (plus the one-time reference). Wire bytes scale with scene
        # activity instead of resolution; the consumer reconstructs exact
        # frames on device (blendjax.ops.tiles <-> data.TileStreamDecoder).
        from blendjax.producer import TileBatchPublisher

        if opts.batch < 2:
            parser.error("--encoding tile requires --batch > 1")
        h, w = opts.shape
        pub = DataPublisher(
            args.btsockets["DATA"], btid=args.btid, lingerms=10000,
            send_hwm=2, trace_every=opts.trace_every,
        )
        if len(opts.tile) > 2:
            parser.error("--tile takes one side or two (rows cols) values")
        tile = opts.tile[0] if len(opts.tile) == 1 else tuple(opts.tile)
        tiles = TileBatchPublisher(
            pub, scene.background_image(), opts.batch, tile=tile,
            alpha_slice=not opts.tile_rgba, ref_interval=opts.ref_interval,
            capacity=opts.tile_capacity or None,
            palette_bits=opts.tile_pal_bits,
        )
        framebuf = np.empty((h, w, 4), np.uint8)
        flush = tiles.flush  # ship trailing frames of a partial batch

        def publish(frame: int) -> None:
            scene.render(out=framebuf)
            tiles.add(
                framebuf,
                # Everything outside the rect the rasterizer just drew is
                # untouched background == the reference: bound the scan.
                hint=scene.raster.last_drawn,
                xy=scene.camera.world_to_pixel(scene.corners_world()).astype(
                    np.float32
                ),
                frameid=np.int64(frame),
            )
            if 0 < opts.frames <= frame:
                ctrl.cancel()

    elif opts.encoding == "pal":
        # Non-sparse lossless codec: palette-compress FULL frames (no
        # reference, no temporal assumption — only "synthetic frames
        # carry few colors"). Per-frame palettes: 16x/8x/4x fewer bytes
        # (2/4/8-bit indices by the widest frame) across the socket AND
        # the host->device link; the consumer decodes with one fused
        # gather on device (blendjax.ops.tiles.palettize_frames).
        # Falls back to a raw batch whenever ANY frame exceeds 256
        # colors.
        from blendjax.ops.tiles import (
            FRAMEPAL_SUFFIXES,
            FRAMESHAPE_SUFFIX,
            PALETTE_SUFFIX,
            palettize_frames,
        )

        if opts.batch < 2:
            parser.error("--encoding pal requires --batch > 1")
        pub = DataPublisher(
            args.btsockets["DATA"], btid=args.btid, lingerms=10000,
            send_hwm=2, trace_every=opts.trace_every,
        )
        b, (h, w) = opts.batch, opts.shape
        buf = {
            "image": np.empty((b, h, w, 4), np.uint8),
            "xy": np.empty((b, 8, 2), np.float32),
            "frameid": np.empty((b,), np.int64),
        }
        cursor = {"i": 0}

        def _ship(filled: dict) -> None:
            # publish() hands ndarrays to the zmq IO thread by REFERENCE
            # (DataPublisher zero-copy contract): anything reused across
            # batches must be copied here, or the next frame's render
            # rewrites bytes of a still-queued message (silent label
            # corruption). packed/pal are fresh allocations per batch;
            # xy/frameid (and the whole buf on palette overflow) are the
            # reused render targets.
            out = palettize_frames(filled["image"])
            if out is None:  # scene outgrew the palette: stay lossless
                pub.publish(
                    _batched=True, **{k: v.copy() for k, v in filled.items()}
                )
                return
            packed, pal, bits = out
            suffix = FRAMEPAL_SUFFIXES[bits]
            pub.publish(
                _prebatched=True,
                **{
                    "image" + suffix: packed,
                    "xy": filled["xy"].copy(),
                    "frameid": filled["frameid"].copy(),
                    "image" + PALETTE_SUFFIX: pal,
                    "image" + FRAMESHAPE_SUFFIX: np.array(
                        [h, w, 4, bits], np.int32
                    ),
                },
            )

        def publish(frame: int) -> None:
            scene.observation_into(frame, buf, cursor["i"])
            cursor["i"] += 1
            if cursor["i"] == b:
                _ship(buf)
                cursor["i"] = 0
            if 0 < opts.frames <= frame:
                ctrl.cancel()

        def flush() -> None:
            i = cursor["i"]
            if i > 0:
                _ship({k: v[:i] for k, v in buf.items()})

    elif opts.batch > 1:
        # Zero-copy batch pool: publish_tracked hands buffers to the socket
        # by reference and returns a zmq MessageTracker; a slot is rendered
        # into again only after its tracker reports the IO thread is done
        # with it. This bounds buffer reuse for any number of connected
        # consumers (per-pipe SNDHWM alone would not: PUSH queues per pipe).
        # A small HWM still provides backpressure (batch messages are
        # ~10MB; 2 batches of queue ≈ the reference's 10-item HWM at
        # batch 8); pool size HWM+2 = queued + in flight + being rendered.
        send_hwm = 2
        pub = DataPublisher(
            args.btsockets["DATA"], btid=args.btid, lingerms=10000,
            send_hwm=send_hwm, trace_every=opts.trace_every,
        )
        b, (h, w) = opts.batch, opts.shape
        pool = [
            {
                "image": np.empty((b, h, w, 4), np.uint8),
                "xy": np.empty((b, 8, 2), np.float32),
                "frameid": np.empty((b,), np.int64),
            }
            for _ in range(send_hwm + 2)
        ]
        trackers = [None] * len(pool)
        cursor = {"slot": 0, "i": 0}

        def publish(frame: int) -> None:
            slot = cursor["slot"]
            if cursor["i"] == 0 and trackers[slot] is not None:
                trackers[slot].wait()  # backpressure: slot still in flight
                trackers[slot] = None
            buf = pool[slot]
            scene.observation_into(frame, buf, cursor["i"])
            cursor["i"] += 1
            if cursor["i"] == b:
                trackers[slot] = pub.publish_tracked(_batched=True, **buf)
                cursor["i"] = 0
                cursor["slot"] = (slot + 1) % len(pool)
            if 0 < opts.frames <= frame:
                ctrl.cancel()

        def flush() -> None:
            # Tail frames of a partial batch (--frames not a multiple of
            # --batch): ship the filled prefix; the consumer's ingest
            # re-batches mismatched sizes.
            i = cursor["i"]
            if i > 0:
                buf = pool[cursor["slot"]]
                pub.publish(_batched=True, **{k: v[:i] for k, v in buf.items()})

    else:
        pub = DataPublisher(
            args.btsockets["DATA"], btid=args.btid, lingerms=10000,
            trace_every=opts.trace_every,
        )

        def publish(frame: int) -> None:
            pub.publish(**scene.observation(frame))
            if 0 < opts.frames <= frame:
                ctrl.cancel()

    ctrl.post_frame.add(publish)
    end = opts.frames if opts.frames > 0 else 2_147_483_647
    try:
        ctrl.play(frame_range=(1, end), num_episodes=-1)
        if flush is not None:
            flush()
    finally:
        pub.close()
        term_context()  # block until the tail is flushed (bounded by linger)


if __name__ == "__main__":
    main()
