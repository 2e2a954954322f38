"""Producer-side sparse streaming: batch + tile-delta-encode + publish.

The producer half of the tile-delta path (``blendjax.ops.tiles``; the
consumer half is ``blendjax.data.TileStreamDecoder``). Feed it one frame
at a time; every ``batch_size`` frames it publishes one pre-batched
message carrying only the tiles that changed vs the reference image —
plus the reference itself, once, in the stream's first message (ZMQ PUSH
is FIFO per producer, so the ref always arrives first).

Wire-size behaviors, all transparent to the consumer:

- **Sticky capacity**: every distinct tile-count capacity is a new array
  shape, and each shape costs one jit compilation of the consumer's
  decode — so the capacity is a per-stream high-water mark (with ~30%
  initial headroom) that only grows on overflow.
- **Sticky index width**: the palette index width (2/4/8 bits) is a wire
  shape too — a batch that needs a wider one than its neighbours breaks
  the consumer's chunk group and compiles its own program — so it is a
  high-water mark as well, starting at ``palette_bits``.
- **Alpha slicing**: when every frame's alpha channel matches the
  reference's (verified per batch), only RGB crosses the wire and the
  consumer restores alpha from the reference — still bit-exact.
"""

from __future__ import annotations

import numpy as np

from blendjax.ops.tiles import (
    PALETTE_SUFFIX,
    TILE,
    TILEIDX_SUFFIX,
    TILEPAL_SUFFIXES,
    TILEREF_SUFFIX,
    TILES_SUFFIX,
    TILESHAPE_SUFFIX,
    TileDeltaEncoder,
    pack_batch,
    pack_palette_indices,
    palettize_tiles,
    tileshape_wire,
)


class TileBatchPublisher:
    """Accumulates frames and publishes tile-delta batch messages.

    ``publisher``: a :class:`blendjax.producer.DataPublisher` (owned by the
    caller; not closed here). ``ref``: the (H, W, C) uint8 reference image
    (typically ``scene.background_image()``). ``field``: the image field
    name the consumer will see after on-device reconstruction.

    ``alpha_slice=False`` keeps full RGBA tiles on the wire even when
    the alpha channel is static (~33% more bytes on the raw-tile wire).
    Since r4 channel-sliced tiles are ALSO Pallas-kernel-eligible (the
    consumer restores the missing channels from the reference on
    device), so the main reason to disable slicing is the fused
    scan+palettize producer path, which needs full-channel tiles and
    ships palette indices — making the channel count nearly free on
    the wire.

    ``ref_interval=N`` re-attaches the reference image every N batches
    (video-keyframe style). With a single consumer the one-shot default
    suffices (PUSH is FIFO per producer), but fair fan-in across several
    consumers/workers delivers the one ref to only one of them — a
    keyframe interval lets the others sync (they skip tile batches until
    a ref arrives) at ~``ref_bytes / N`` amortized overhead.

    ``palette=True`` (default) palette-compresses tile payloads when
    changed tiles hold few distinct colors (flat-shaded frames usually
    do): <=4 colors ship as 2-bit indices (16x fewer bytes), <=16 as
    4-bit (8x), <=256 as bytes (4x); more falls back to raw tiles. Lossless either way — the
    consumer's decode gathers through the palette on device. With
    full-channel tiles (``alpha_slice=False``) and the native helpers
    available, palettization FUSES into the changed-tile scan (one
    pass, no raw-tile materialization) with PER-FRAME color tables:
    each row of the batch ships its own palette (the wire carries a
    ``(B, cap, C)`` palette array), so a single frame's color count —
    not the whole batch's — picks the index width; a >256-color frame
    falls back to raw tiles transparently.

    ``capacity`` pins the per-frame tile capacity from the first batch
    (it still grows on overflow). Every distinct capacity is a distinct
    wire/array shape — one consumer decode compilation, and a chunk-group
    boundary — so a fleet of producers streaming the same scene should
    share an explicit capacity rather than each settling its own
    high-water mark. ``palette_bits`` (2, 4 or 8) does the same for the
    palette index width: the narrowest the stream will ship, growing
    (and staying grown) when a frame needs more colors. A fleet pins it
    to what its scene's busiest frame needs: one frame in a few hundred
    of the cube scene holds a fifth color, and at the default 2 each
    such batch would cost the consumer a short chunk group and a
    compile.
    """

    def __init__(self, publisher, ref: np.ndarray, batch_size: int,
                 tile=TILE, field: str = "image",
                 alpha_slice: bool = True, ref_interval: int = 0,
                 palette: bool = True, capacity: int | None = None,
                 palette_bits: int = 2):
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if palette_bits not in (2, 4, 8):
            raise ValueError(f"palette_bits must be 2, 4 or 8, got {palette_bits}")
        self._palette_bits = int(palette_bits)  # sticky: only grows
        self.publisher = publisher
        self.batch_size = int(batch_size)
        self.field = field
        self.alpha_slice = bool(alpha_slice)
        self.ref_interval = max(0, int(ref_interval))
        self.palette = bool(palette)
        self._palette_misses = 0  # latch: stop paying the scan if futile
        self.encoder = TileDeltaEncoder(ref, tile=tile)
        # tile pixel dims: int side, or (th, tw) — rectangular (16, 32)
        # tiles at C=4 unlock the consumer's direct-spatial decode
        self.th, self.tw = self.encoder.th, self.encoder.tw
        self._ref = self.encoder.ref
        if self._ref.shape[2] == 4:
            # Tiled view of the reference's alpha plane, indexed by flat
            # tile id — the alpha-static check then touches only the
            # tiles each frame actually changed.
            gh, gw = self.encoder.grid
            th, tw = self.th, self.tw
            self._ref_tile_alpha = np.ascontiguousarray(
                self._ref[:, :, 3]
                .reshape(gh, th, gw, tw)
                .transpose(0, 2, 1, 3)
                .reshape(gh * gw, th, tw)
            )
        else:
            self._ref_tile_alpha = None
        self._deltas: list = []
        self._extras: dict = {}
        self._alpha_static = True
        self._ref_sent = False
        self._capacity: int | None = (
            min(int(capacity), self.encoder.num_tiles)
            if capacity else None
        )
        self.batches_published = 0
        # Direct-pack fast path: once the capacity is fixed, frames
        # encode straight into these (B, cap, ...) batch arrays — one
        # copy per frame (staging -> row) instead of the buffered path's
        # two plus two allocations. The arrays never leave the process
        # (publish ships palette-packed or copied views), so one set is
        # safe to reuse across batches even with zero-copy sends.
        self._batch_idx: np.ndarray | None = None
        self._batch_tiles: np.ndarray | None = None
        self._row = 0
        # Fused scan+palettize (encoder.encode_palidx, native): one pass
        # both finds changed tiles and emits PER-FRAME palette indices
        # (the table resets at each frame, so neither color drift across
        # a batch nor across an animation can exhaust it) — the separate
        # whole-batch palettize pass and the raw-tile materialization
        # disappear.
        # Engages when palettization is on and full-channel tiles stream
        # (alpha slicing needs raw tiles for its check); a >256-color
        # batch falls back to raw tiles, repeated fallbacks latch the
        # path off like the two-pass miss latch.
        self._fused_ok = (
            self.palette
            # alpha slicing is inert without an alpha plane: RGB streams
            # keep the fused path under the default alpha_slice=True
            and not (self.alpha_slice and self._ref_tile_alpha is not None)
            and self.encoder.palidx_available()
        )
        self._raw_batch = False  # this batch fell back to raw tiles
        self._batch_pal: np.ndarray | None = None
        # per-row palette snapshots (fused path): colors + counts per
        # frame of the current batch
        self._row_pals: list = [None] * self.batch_size
        self._row_counts: list = [0] * self.batch_size

    def add(self, image: np.ndarray, hint=None, **extras) -> None:
        """Add one frame plus its per-frame sidecar fields (annotations,
        frame ids, ...); publishes automatically when the batch fills.
        ``hint`` optionally bounds the changed-tile scan to a pixel rect
        (see :meth:`TileDeltaEncoder.encode`)."""
        if (
            self._fused_ok
            and not self._raw_batch
            and self._capacity is not None
        ):
            # PER-FRAME palette: each frame indexes its own fresh table,
            # so a single frame's color count (not the whole batch's)
            # decides 4-bit vs 8-bit packing — flat-shaded scenes whose
            # batches drift past 16 colors still ship nibbles (halves
            # the dominant wire term). The per-row palettes ride the
            # wire as one (B, cap, C) array.
            self.encoder.reset_palette()
            out = self.encoder.encode_palidx(image, hint=hint)
            if out is not None:
                fi, fpal = out
                k = len(fi)
                if k > self._capacity:
                    self._grow(k)
                self._ensure_batch_arrays()
                i = self._row
                self._batch_idx[i, :k] = fi
                self._batch_idx[i, k:] = self.encoder.num_tiles
                self._batch_pal[i, :k] = fpal
                self._batch_pal[i, k:] = 0
                self._row_counts[i] = self.encoder.palette_count
                self._row_pals[i] = self.encoder.palette[
                    : self.encoder.palette_count
                ].copy()
                self._row += 1
                for key, v in extras.items():
                    self._extras.setdefault(key, []).append(v)
                if self._row == self.batch_size:
                    self._publish()
                return
            # >256 colors in this batch: reconstruct raw tiles for the
            # rows already packed and finish the batch raw (batch-level
            # palettize may still engage at publish). Repeated overflows
            # latch the fused path off like the two-pass miss latch.
            self._raw_batch = True
            self._palette_misses += 1
            if self._palette_misses >= 8:
                self._fused_ok = False
            self._depalettize_rows()
        fi, ft = self.encoder.encode(image, hint=hint)
        if self._ref_tile_alpha is not None and self._alpha_static:
            # Unchanged tiles are byte-identical to the ref by definition,
            # so whole-frame alpha equality reduces to the changed tiles.
            self._alpha_static = np.array_equal(
                ft[..., 3], self._ref_tile_alpha[fi]
            )
        if self._capacity is not None:
            k = len(fi)
            if k > self._capacity:
                self._grow(k)
            self._ensure_batch_arrays()
            i = self._row
            self._batch_idx[i, :k] = fi
            self._batch_idx[i, k:] = self.encoder.num_tiles  # sentinel
            self._batch_tiles[i, :k] = ft
            self._batch_tiles[i, k:] = 0
            self._row += 1
        else:
            # No pinned capacity yet: buffer the first batch's deltas,
            # _publish fixes the sticky capacity, and every later frame
            # takes the direct path above.
            self._deltas.append((fi.copy(), ft.copy()))
        for key, v in extras.items():
            self._extras.setdefault(key, []).append(v)
        if self._row + len(self._deltas) == self.batch_size:
            self._publish()

    def _ensure_batch_arrays(self) -> None:
        if self._batch_idx is None:
            c = self._ref.shape[2]
            self._batch_idx = np.empty(
                (self.batch_size, self._capacity), np.int32
            )
            self._batch_tiles = np.empty(
                (self.batch_size, self._capacity, self.th, self.tw, c),
                np.uint8,
            )
        if self._fused_ok and self._batch_pal is None:
            self._batch_pal = np.empty(
                (self.batch_size, self._capacity, self.th * self.tw),
                np.uint8,
            )

    def _grow(self, kmax: int) -> None:
        """Overflow: widen the sticky capacity (32-tile steps) and
        migrate any rows already packed this batch."""
        new_cap = min(-(-kmax // 32) * 32, self.encoder.num_tiles)
        old_idx, old_tiles, n = self._batch_idx, self._batch_tiles, self._row
        old_pal = self._batch_pal
        self._capacity = new_cap
        self._batch_idx = None
        self._batch_pal = None
        self._ensure_batch_arrays()
        if n and old_idx is not None:
            self._batch_idx[:n, : old_idx.shape[1]] = old_idx[:n]
            self._batch_idx[:n, old_idx.shape[1]:] = self.encoder.num_tiles
            self._batch_tiles[:n, : old_tiles.shape[1]] = old_tiles[:n]
            self._batch_tiles[:n, old_tiles.shape[1]:] = 0
            if old_pal is not None and self._batch_pal is not None:
                self._batch_pal[:n, : old_pal.shape[1]] = old_pal[:n]
                self._batch_pal[:n, old_pal.shape[1]:] = 0

    def _depalettize_rows(self) -> None:
        """Fused -> raw fallback mid-batch: reconstruct raw tiles for the
        rows already packed as palette indices (lossless gather). Each
        row gathers through ITS OWN per-frame palette snapshot."""
        n = self._row
        if not n or self._batch_pal is None:
            return
        self._ensure_batch_arrays()
        c = self._ref.shape[2]
        for i in range(n):
            colors = np.zeros((256, c), np.uint8)
            rp = self._row_pals[i]
            if rp is not None:
                colors[: len(rp)] = rp
            self._batch_tiles[i] = colors[self._batch_pal[i]].reshape(
                self._capacity, self.th, self.tw, c
            )
        # padding slots must ship zeroed tiles (pack contract), not
        # palette color 0
        pad = self._batch_idx[:n] == self.encoder.num_tiles
        self._batch_tiles[:n][pad] = 0

    def flush(self) -> None:
        """Publish any buffered partial batch (call when a finite stream
        ends so trailing frames aren't dropped; the consumer's ingest
        passes the ragged batch through)."""
        if self._deltas or self._row:
            self._publish()

    def _finish_publish(self, msg: dict) -> None:
        """Shared tail of both publish forms: sidecar extras, keyframe
        reference attachment, per-batch state reset, publish."""
        for k, vals in self._extras.items():
            msg[k] = np.stack([np.asarray(v) for v in vals])
        keyframe = (
            self.ref_interval > 0
            and self.batches_published % self.ref_interval == 0
        )
        if not self._ref_sent or keyframe:
            msg[self.field + TILEREF_SUFFIX] = self._ref
            self._ref_sent = True
        self._deltas.clear()
        self._extras = {}
        self._alpha_static = True
        self._row = 0
        self._raw_batch = False
        self._row_pals = [None] * self.batch_size
        self._row_counts = [0] * self.batch_size
        self.publisher.publish(**msg)
        self.batches_published += 1

    def _publish(self) -> None:
        if (
            self._fused_ok
            and not self._raw_batch
            and self._row
            and not self._deltas
        ):
            # Fused path: rows are already palette indices against the
            # encoder's per-batch table — no raw tiles ever materialized.
            n = self._row
            h, w, c = self._ref.shape
            idx = self._batch_idx[:n].copy()
            pal_idx = self._batch_pal[:n]
            # palette success resets the miss latch (matching the
            # two-pass path; an overflow-only latch would defeat it)
            self._palette_misses = 0
            # Per-frame palettes: the LARGEST row count picks the index
            # width for the whole batch (one wire shape), but each row
            # ships (and the consumer gathers through) its own colors.
            counts = self._row_counts[:n]
            cmax = max(counts) if counts else 0
            tt = self.th * self.tw
            if cmax <= 4 and tt % 4 == 0:
                # four 2-bit indices per byte (flat-shaded frames often
                # hold <=4 colors: background + a few faces)
                needed = 2
            elif cmax <= 16 and tt % 2 == 0:
                needed = 4
            else:
                needed = 8
            bits = self._palette_bits = max(self._palette_bits, needed)
            cap_colors = 1 << bits
            suffix = TILEPAL_SUFFIXES[bits]
            # fresh allocation either way: pal_idx is a reused batch
            # array and publish hands buffers to the IO thread by ref
            packed = (
                pack_palette_indices(pal_idx, bits)
                if bits < 8 else pal_idx.copy()
            )
            # (B, cap, C), zero-padded past each row's count (the wire
            # contract; row tables are snapshots taken per frame)
            pal = np.zeros((n, cap_colors, c), np.uint8)
            for i in range(n):
                pal[i, : counts[i]] = self._row_pals[i]
            self._finish_publish({
                "_prebatched": True,
                self.field + TILEIDX_SUFFIX: idx,
                self.field + TILESHAPE_SUFFIX: tileshape_wire(
                    h, w, c, (self.th, self.tw)
                ),
                self.field + suffix: packed,
                self.field + PALETTE_SUFFIX: pal,
            })
            return
        if self._deltas:
            # First batch without a pinned capacity: fix the sticky
            # capacity BEFORE the pack so every message of the stream
            # (first included) shares one shape = one consumer decode
            # compilation; grow in 32-tile steps only on overflow.
            kmax = max((len(i) for i, _ in self._deltas), default=0)
            if self._capacity is None:
                kmax = max(int(kmax * 1.3), 1)
            if self._capacity is None or kmax > self._capacity:
                self._capacity = min(
                    -(-kmax // 32) * 32, self.encoder.num_tiles
                )
            idx, tiles = pack_batch(
                self._deltas, self.encoder.num_tiles,
                capacity=self._capacity,
            )
            fresh = True  # pack_batch allocated these; safe to ship
        else:
            n = self._row
            # idx is tiny (~KB): copy so the reused batch array never
            # rides a zero-copy send. tiles is copied below only on the
            # raw-wire path (the palette path ships fresh arrays).
            idx = self._batch_idx[:n].copy()
            tiles = self._batch_tiles[:n]
            fresh = False
        if (
            self.alpha_slice
            and self._alpha_static
            and self._ref_tile_alpha is not None
        ):
            tiles = np.ascontiguousarray(tiles[..., :3])
            fresh = True
        h, w, c = self._ref.shape
        msg = {
            "_prebatched": True,
            self.field + TILEIDX_SUFFIX: idx,
            self.field + TILESHAPE_SUFFIX: tileshape_wire(
                h, w, c, (self.th, self.tw)
            ),
        }
        compressed = (
            palettize_tiles(tiles, min_bits=self._palette_bits)
            if self.palette else None
        )
        if compressed is not None:
            self._palette_misses = 0
            packed, pal, bits = compressed
            self._palette_bits = bits
            suffix = TILEPAL_SUFFIXES[bits]
            msg[self.field + suffix] = packed
            msg[self.field + PALETTE_SUFFIX] = pal
        else:
            if self.palette:
                # Color-rich scene: after enough consecutive misses stop
                # paying the palette scan on every batch.
                self._palette_misses += 1
                if self._palette_misses >= 8:
                    self.palette = False
            msg[self.field + TILES_SUFFIX] = tiles if fresh else tiles.copy()
        self._finish_publish(msg)
