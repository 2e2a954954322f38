"""Lossless tile-delta encoding for image streams.

Synthetic-render streams are sparse: between frames (or against a static
background) only the pixels the scene geometry touches change. The
reference ships every frame as a full pickled RGBA buffer
(``publisher.py:43`` -> ``dataset.py:105``); on a TPU host the equivalent
raw stream is bounded by host->HBM transfer bandwidth long before the chip
is busy. This module moves the bottleneck: producers send only the tiles
that differ from a *reference image* (typically the scene background), and
the consumer reconstructs exact full frames **on device** with a jitted
batched scatter — so the bytes that cross the host->device boundary scale
with scene activity, not resolution.

Encoding (host side, producer):
    ``TileDeltaEncoder(ref).encode(img)`` -> ``(idx, tiles)`` where ``idx``
    holds flattened tile indices (row-major over the tile grid) and
    ``tiles`` the changed ``t x t x C`` blocks; ``pack_batch`` pads frames
    to a shared capacity with the sentinel index ``num_tiles`` which the
    device scatter drops.

Decoding (device side, consumer):
    ``ref_tiles = tile_ref(ref)`` once per stream, then
    ``decode_tile_delta(ref_tiles, idx, tiles, shape=...)`` per batch:
    a ``vmap``-ed ``.at[idx].set(tiles, mode='drop')`` scatter plus a
    reshape back to NHWC. Exact reconstruction — ``decode(encode(x)) == x``
    bit-for-bit (asserted by ``tests/test_tiles.py``).

Wire convention (understood by ``blendjax.data.StreamDataPipeline`` and
the torch adapter; full table in ``docs/wire-protocol.md``): for an image
field ``name`` a tile-encoded batch message carries ``name__tileidx``
(B, K) int32, ``name__tileshape`` — the 5-element rectangular form
[H, W, C, th, tw] (tiles are th x tw x C blocks, row-major over the
ceil(H/th) x ceil(W/tw) grid; see ``geom_tile``; consumers also accept
the legacy square v1 form [H, W, C, t] = th == tw == t) — and the tile
payload: ``name__tiles`` (B, K, th, tw, C) uint8 raw, or the
palette-compressed ``name__tilepal2``/``4``/``8`` + ``name__palette``
when the batch's colors fit 2/4/8-bit indices. The reference image
travels as ``name__tileref`` (H, W, C) in the producer's first message —
and, when ``TileBatchPublisher(ref_interval=N)`` is set (default off),
every Nth batch as a keyframe so late-joining consumers can sync.

The changed-tile scan runs in C++ when the native helper builds
(``blendjax/_native/tiledelta.cpp``); the numpy fallback is identical.
"""

from __future__ import annotations

import numpy as np

from blendjax.utils.metrics import (
    KERNEL_TILE_DECODE_SCATTER,
    KERNEL_TILE_DECODE_SPATIAL,
    SCOPE_PALETTE_EXPAND,
)

TILE = 32  # default tile side; must divide both image dims

TILEIDX_SUFFIX = "__tileidx"
TILES_SUFFIX = "__tiles"
TILESHAPE_SUFFIX = "__tileshape"
TILEREF_SUFFIX = "__tileref"
# palette-compressed tile payloads (PNG-8 style; lossless):
TILEPAL2_SUFFIX = "__tilepal2"   # four 2-bit palette indices per byte
TILEPAL4_SUFFIX = "__tilepal4"   # two 4-bit palette indices per byte
TILEPAL8_SUFFIX = "__tilepal8"   # one byte per pixel
PALETTE_SUFFIX = "__palette"     # (cap, C) or per-row (B, cap, C)
#                                  uint8, zero-padded past used entries
# palette-compressed FULL frames (the non-sparse codec: no reference
# frame, no temporal assumption — see palettize_frames):
FRAMEPAL2_SUFFIX = "__framepal2"  # (B, H*W/4) 2-bit indices
FRAMEPAL4_SUFFIX = "__framepal4"  # (B, H*W/2) nibble indices
FRAMEPAL8_SUFFIX = "__framepal8"  # (B, H*W) byte indices
FRAMESHAPE_SUFFIX = "__frameshape"  # [H, W, C, bits]

FRAMEPAL_SUFFIXES = {
    2: FRAMEPAL2_SUFFIX, 4: FRAMEPAL4_SUFFIX, 8: FRAMEPAL8_SUFFIX,
}
TILEPAL_SUFFIXES = {
    2: TILEPAL2_SUFFIX, 4: TILEPAL4_SUFFIX, 8: TILEPAL8_SUFFIX,
}


def pack_palette_indices(idx, bits: int):
    """Pack uint8 palette indices along the LAST axis: 4 per byte for
    ``bits=2``, 2 per byte for ``bits=4``, pass-through for ``bits=8``.
    The single definition of the bit order (first index in the high
    bits) — every producer packs and every consumer unpacks through
    this pair, so the wire variants stay in one place."""
    if bits == 2:
        return (
            (idx[..., 0::4] << 6) | (idx[..., 1::4] << 4)
            | (idx[..., 2::4] << 2) | idx[..., 3::4]
        )
    if bits == 4:
        return (idx[..., 0::2] << 4) | idx[..., 1::2]
    return idx


def unpack_palette_indices(packed, bits: int):
    """Inverse of :func:`pack_palette_indices`, on the host (the device
    cuts the indices out lane by lane: :func:`_expand_palette`)."""
    lead = packed.shape[:-1]
    m = packed.shape[-1]
    if bits == 2:
        return np.stack(
            [packed >> 6, (packed >> 4) & 3, (packed >> 2) & 3,
             packed & 3],
            axis=-1,
        ).reshape(*lead, m * 4)
    if bits == 4:
        return np.stack(
            [packed >> 4, packed & 0xF], axis=-1
        ).reshape(*lead, m * 2)
    return packed


def tile_hw(tile):
    """Normalize a tile spec — an int side or a ``(rows, cols)`` pair —
    to ``(th, tw)`` pixel dims.

    Rectangular tiles exist for the decoder's benefit: a (16, 32) tile
    at C=4 spans exactly 128 output lanes (the TPU's native lane
    width), which unlocks the direct-spatial Pallas decode
    (:func:`_pallas_decode_spatial`: no slot buffer, no reference-
    broadcast init pass, no tile->frame transpose pass).
    """
    if isinstance(tile, (tuple, list, np.ndarray)):
        if len(tile) != 2:
            raise ValueError(
                f"tile spec must be an int or (th, tw), got {tile!r}"
            )
        return int(tile[0]), int(tile[1])
    return int(tile), int(tile)


def geom_tile(geom):
    """Wire-geometry tuple -> ``(th, tw)`` tile pixel dims: the square
    v1 form is ``[h, w, c, t]``, the rectangular form ``[h, w, c, th,
    tw]`` (see :func:`tileshape_wire`)."""
    if len(geom) >= 5:
        return int(geom[3]), int(geom[4])
    return int(geom[3]), int(geom[3])


def tileshape_wire(h, w, c, tile):
    """Geometry -> the wire ``__tileshape`` list. Square tiles keep the
    4-element v1 form so consumers of either vintage decode square
    streams; rectangular tiles use the 5-element form."""
    th, tw = tile_hw(tile)
    base = [int(h), int(w), int(c), th]
    return base if th == tw else base + [tw]


def tile_grid(shape, tile=TILE):
    """(H, W, C) image shape -> (GH, GW) tile-grid shape.

    ``tile`` is an int side or a ``(th, tw)`` pair. Raises if the tile
    size does not divide the image dims (callers should fall back to
    raw frames for such shapes).
    """
    th, tw = tile_hw(tile)
    h, w = int(shape[0]), int(shape[1])
    if h % th or w % tw:
        raise ValueError(f"tile {th}x{tw} does not divide image {h}x{w}")
    return h // th, w // tw


class TileDeltaEncoder:
    """Per-stream host-side encoder: images -> (idx, tiles) deltas.

    Holds the reference image and preallocated staging buffers so the
    per-frame cost is one changed-tile scan plus copies of only the
    changed tiles. Use one encoder per stream/scene.
    """

    def __init__(self, ref: np.ndarray, tile=TILE):
        ref = np.ascontiguousarray(ref)
        if ref.dtype != np.uint8 or ref.ndim != 3:
            raise ValueError(f"ref must be (H, W, C) uint8, got {ref.shape} {ref.dtype}")
        self.ref = ref
        self.th, self.tw = tile_hw(tile)
        self.tile = tile  # original spec (int or pair), for repr/pickle
        self.grid = tile_grid(ref.shape, (self.th, self.tw))
        self.num_tiles = self.grid[0] * self.grid[1]
        h, w, c = ref.shape
        self._idx = np.empty((self.num_tiles,), np.int32)
        self._tiles = np.empty((self.num_tiles, self.th, self.tw, c), np.uint8)
        from blendjax._native import load_tile_delta

        self._native = load_tile_delta()
        self._native_palidx = None  # resolved on first encode_palidx
        self._pal_state = None

    def _check_frame(self, img: np.ndarray) -> None:
        if img.shape != self.ref.shape or img.dtype != np.uint8:
            raise ValueError(
                f"frame shape {img.shape}/{img.dtype} != ref "
                f"{self.ref.shape}/uint8"
            )

    def __getstate__(self):
        """Copy/pickle safety: drop the native handles (ctypes functions
        don't pickle) and the palette state — its cached raw buffer
        addresses would alias the ORIGINAL encoder's buffers in a
        deepcopy, or point at garbage in a spawned process. Both rebuild
        lazily."""
        state = dict(self.__dict__)
        state["_native"] = None
        state["_native_palidx"] = None
        state["_pal_state"] = None
        # staging buffers are uninitialized scratch (MBs for large
        # streams) — drop them too; shapes re-derive from ref/tile
        state.pop("_palidx_stage", None)
        state.pop("_idx", None)
        state.pop("_tiles", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        from blendjax._native import load_tile_delta

        self._native = load_tile_delta()
        c = self.ref.shape[2]
        self._idx = np.empty((self.num_tiles,), np.int32)
        self._tiles = np.empty(
            (self.num_tiles, self.th, self.tw, c), np.uint8
        )

    def tile_bounds(self, hint):
        """Pixel-rect ``hint`` -> tile-grid scan bounds
        ``(ty0, ty1, tx0, tx1)`` (full grid for ``hint=None``)."""
        th, tw = self.th, self.tw
        gh, gw = self.grid
        if hint is None:
            return 0, gh, 0, gw
        y0, y1, x0, x1 = hint
        return (
            max(y0 // th, 0), min(-(-y1 // th), gh),
            max(x0 // tw, 0), min(-(-x1 // tw), gw),
        )

    def encode(self, img: np.ndarray, hint=None):
        """One frame -> ``(idx int32[K], tiles uint8[K, t, t, C])`` views
        into internal staging (valid until the next ``encode`` call).

        ``hint`` is an optional pixel rect ``(y0, y1, x0, x1)`` promising
        that pixels outside it equal the reference (e.g. the rasterizer's
        ``last_drawn`` dirty rect) — the scan then touches only the tiles
        the rect overlaps. ``hint=None`` scans the full frame.
        """
        th, tw = self.th, self.tw
        h, w, c = self.ref.shape
        gh, gw = self.grid
        self._check_frame(img)
        ty0, ty1, tx0, tx1 = self.tile_bounds(hint)
        if ty0 >= ty1 or tx0 >= tx1:
            return self._idx[:0], self._tiles[:0]
        if self._native is not None and img.flags.c_contiguous:
            import ctypes

            u8 = ctypes.POINTER(ctypes.c_uint8)
            count = self._native(
                img.ctypes.data_as(u8),
                self.ref.ctypes.data_as(u8),
                h, w, c, th, tw, ty0, ty1, tx0, tx1,
                self._idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                self._tiles.ctypes.data_as(u8),
            )
            return self._idx[:count], self._tiles[:count]
        v = img.reshape(gh, th, gw, tw, c)
        r = self.ref.reshape(gh, th, gw, tw, c)
        sub = (v[ty0:ty1, :, tx0:tx1] != r[ty0:ty1, :, tx0:tx1]).any(
            axis=(1, 3, 4)
        )  # (ty1-ty0, tx1-tx0)
        sy, sx = np.nonzero(sub)
        idx = ((sy + ty0) * gw + (sx + tx0)).astype(np.int32)
        k = len(idx)
        self._idx[:k] = idx
        # Advanced indexing (rows, :, cols) puts the K axis first -> (K,th,tw,C).
        self._tiles[:k] = v[idx // gw, :, idx % gw]
        return self._idx[:k], self._tiles[:k]

    # -- fused scan + palettize (native only) -------------------------------

    def palidx_available(self) -> bool:
        """True when the fused scan+palettize (``encode_palidx``) can run
        (native helpers built, <= 4 channels)."""
        if self.ref.shape[2] > 4:
            return False
        if self._native_palidx is None:
            from blendjax._native import load_tile_delta_palidx

            self._native_palidx = load_tile_delta_palidx()
        return self._native_palidx is not None

    def reset_palette(self) -> None:
        """Clear the palette table (call at each batch boundary so
        color-drifting scenes never exhaust the 256 entries)."""
        if self._pal_state is not None:
            self._pal_state["vals"].fill(-1)
            self._pal_state["count"][0] = 0

    @property
    def palette(self) -> np.ndarray:
        """(256, C) uint8 palette filled up to ``palette_count``."""
        return self._pal_state["table"]

    @property
    def palette_count(self) -> int:
        return int(self._pal_state["count"][0]) if self._pal_state else 0

    def encode_palidx(self, img: np.ndarray, hint=None):
        """One frame -> ``(idx int32[K], palidx uint8[K, t*t])`` views
        into internal staging — the fused form of :meth:`encode` that
        emits palette indices against the encoder's palette table
        instead of raw tiles (one pass; no tile materialization).

        Returns ``None`` when a pixel would push the table past 256
        colors — the caller falls back to :meth:`encode` (the table
        state stays valid). The caller owns the reset policy via
        :meth:`reset_palette` (TileBatchPublisher resets per frame and
        ships per-row palette snapshots).
        """
        if not self.palidx_available():
            return None
        self._check_frame(img)
        img = np.ascontiguousarray(img)
        h, w, c = self.ref.shape
        if self._pal_state is None:
            s = {
                "keys": np.zeros(1024, np.uint32),
                "vals": np.full(1024, -1, np.int16),
                "table": np.zeros((256, c), np.uint8),
                "count": np.zeros(1, np.int64),
            }
            self._palidx_stage = np.empty(
                (self.num_tiles, self.th * self.tw), np.uint8
            )
            # Pointers to the persistent buffers are cached as plain
            # ints (the native argtypes are void*): re-marshalling 8
            # ctypes pointer objects per frame costs ~0.05ms — real
            # money in a ~1ms/frame producer loop.
            s["ptrs"] = (
                self.ref.ctypes.data,
                self._idx.ctypes.data,
                self._palidx_stage.ctypes.data,
                s["keys"].ctypes.data,
                s["vals"].ctypes.data,
                s["table"].ctypes.data,
                s["count"].ctypes.data,
            )
            self._pal_state = s
        ty0, ty1, tx0, tx1 = self.tile_bounds(hint)
        (p_ref, p_idx, p_stage, p_keys, p_vals, p_table, p_count
         ) = self._pal_state["ptrs"]
        k = self._native_palidx(
            img.ctypes.data, p_ref,
            h, w, c, self.th, self.tw, ty0, ty1, tx0, tx1,
            p_idx, p_stage, p_keys, p_vals, p_table, p_count,
            256,
        )
        if k < 0:
            return None
        return self._idx[:k], self._palidx_stage[:k]


def pack_batch(deltas, num_tiles: int, bucket: int = 16, capacity=None):
    """Pack per-frame ``(idx, tiles)`` deltas into fixed-capacity batch
    arrays.

    Every distinct capacity is a distinct ``(B, K, ...)`` shape, and each
    shape costs one jit compilation of the consumer's decode — so stable
    capacities matter more than tight ones. Pass ``capacity`` (a sticky
    per-stream value the producer grows only on overflow) to pin the
    shape; without it, capacity is the batch's largest per-frame tile
    count rounded up to a multiple of ``bucket``. Padding slots carry the
    sentinel index ``num_tiles`` which the device scatter drops.

    Returns ``(idx (B, K) int32, tiles (B, K, t, t, C) uint8)``.
    """
    b = len(deltas)
    kmax = max((len(i) for i, _ in deltas), default=0)
    bucket = max(int(bucket), 1)
    if capacity is not None and int(capacity) >= kmax:
        cap = int(capacity)
    else:
        cap = max(-(-kmax // bucket) * bucket, bucket)
    cap = min(cap, num_tiles)
    th, tw, c = deltas[0][1].shape[1], deltas[0][1].shape[2], deltas[0][1].shape[3]
    idx = np.full((b, cap), num_tiles, np.int32)
    tiles = np.empty((b, cap, th, tw, c), np.uint8)
    for i, (fi, ft) in enumerate(deltas):
        k = len(fi)
        idx[i, :k] = fi
        tiles[i, :k] = ft
        tiles[i, k:] = 0  # don't ship uninitialized heap bytes in padding
    return idx, tiles


def pop_stream_refs(msg: dict, refs: dict, btid) -> None:
    """Pop every ``<name>__tileref`` entry of a message into ``refs``
    keyed ``(name, btid)`` — the shared wire-convention bookkeeping for
    all tile-stream consumers (device pipeline and torch adapter)."""
    for key in [k for k in msg if k.endswith(TILEREF_SUFFIX)]:
        refs[(key[: -len(TILEREF_SUFFIX)], btid)] = msg.pop(key)


def pop_tile_batches(msg: dict):
    """Pop tile-delta geometry entries from a message.

    Returns ``[(name, geom), ...]`` — empty for non-tile messages —
    where ``geom`` is the wire tuple ``(h, w, c, t)`` for square tiles
    or ``(h, w, c, th, tw)`` for rectangular ones (decode the tile dims
    with :func:`geom_tile`, never by indexing position 3). The payload fields (``__tileidx`` plus ``__tiles`` or the
    palette-compressed ``__tilepal4/8`` + ``__palette``) stay in the
    message for the caller to transfer/decode. Callers look refs up
    under ``(name, btid)`` and should SKIP (not fail) messages whose ref
    hasn't arrived yet: with fair fan-in across multiple consumers, the
    one-time (or keyframe-interval) reference lands on one consumer's
    socket at a time.
    """
    out = []
    for key in [k for k in msg if k.endswith(TILESHAPE_SUFFIX)]:
        name = key[: -len(TILESHAPE_SUFFIX)]
        out.append((name, tuple(int(v) for v in msg.pop(key))))
    return out


def pop_tile_payload(fields: dict, name: str, geom, expand):
    """Pop ``name``'s tile payload from ``fields`` and return the
    expanded (K-leading) tile array, where ``expand`` is
    :func:`expand_palette_tiles` (device) or
    :func:`expand_palette_tiles_np` (host). Shared by every consumer so
    the raw-vs-palette wire variants stay in one place."""
    t = geom_tile(geom)
    for bits, suffix in TILEPAL_SUFFIXES.items():
        if name + suffix in fields:
            packed = fields.pop(name + suffix)
            pal = fields.pop(name + PALETTE_SUFFIX)
            return expand(packed, pal, bits, t, pal.shape[-1])
    return fields.pop(name + TILES_SUFFIX)


def decode_tile_delta_np(ref: np.ndarray, idx: np.ndarray,
                         tiles: np.ndarray, tile=None) -> np.ndarray:
    """Host-side (numpy) reconstruction — for consumers that never touch
    a device, e.g. the torch-compat dataset adapter. Same semantics as
    :func:`decode_tile_delta`: sentinel indices are dropped, channel-
    sliced tiles restore their remaining channels from the reference.

    ``idx``: (B, K) int32; ``tiles``: (B, K, th, tw, Ct) — the tile
    pixel dims come from the tiles array itself (``tile`` is accepted
    for back-compat and ignored). Returns (B, H, W, C) uint8, bit-exact.
    """
    del tile
    h, w, c = ref.shape
    th, tw = tiles.shape[2], tiles.shape[3]
    gh, gw = tile_grid(ref.shape, (th, tw))
    n = gh * gw
    b = idx.shape[0]
    ct = tiles.shape[-1]
    out = np.broadcast_to(ref, (b, h, w, c)).copy()
    ov = out.reshape(b, gh, th, gw, tw, c)
    for bi in range(b):
        # Positional like the device decoder: mask BOTH idx and tiles so
        # sentinels anywhere (not just a suffix) pair correctly.
        m = idx[bi] < n
        real = idx[bi][m]
        # (K,) flat ids -> rows/cols; advanced indexing puts K first
        ov[bi, real // gw, :, real % gw, :, :ct] = tiles[bi][m]
    return out


# -- palette compression (host encode / device expand) ----------------------
#
# Flat-shaded synthetic frames carry very few distinct colors, so the
# changed tiles compress losslessly to palette indices: <=16 colors ->
# two 4-bit indices per byte (8x fewer bytes than RGBA), <=256 -> one
# byte per pixel (4x). The device expands 2- and 4-bit indices by dense
# arithmetic and selects, 8-bit ones by a gather (:func:`_expand_palette`).


def _palettize_flat(flat: np.ndarray, max_colors: int):
    """Core palette pass over (N, C) uint8 pixels: returns
    ``(idx (N,) uint8, palette (max_colors, C), count)`` or ``None``
    when the pixels hold more than ``max_colors`` distinct colors.
    One native C pass when available; numpy fallback."""
    from blendjax._native import load_palettize

    n, c = flat.shape
    native = load_palettize()
    if native is not None:
        import ctypes

        pal = np.zeros((max_colors, c), np.uint8)
        idx = np.empty((n,), np.uint8)
        u8 = ctypes.POINTER(ctypes.c_uint8)
        count = native(
            flat.ctypes.data_as(u8), n, c, max_colors,
            pal.ctypes.data_as(u8), idx.ctypes.data_as(u8),
        )
        if count < 0:
            return None
        return idx, pal, count
    key = np.zeros(n, np.uint32)
    for j in range(c):
        key |= flat[:, j].astype(np.uint32) << (8 * j)
    uniq, idx32 = np.unique(key, return_inverse=True)
    count = len(uniq)
    if count > max_colors:
        return None
    idx = idx32.astype(np.uint8)
    pal = np.zeros((max_colors, c), np.uint8)
    for j in range(c):
        pal[:count, j] = (uniq >> (8 * j)).astype(np.uint8)
    return idx, pal, count


def palettize_tiles(tiles: np.ndarray, max_colors: int = 256,
                    min_bits: int = 2):
    """Try to palette-compress a packed tile array (B, K, t, t, C).

    Returns ``(packed, palette, bits)`` — ``packed`` is
    (B, K, t*t/4 | t*t/2 | t*t) uint8 for ``bits`` 2/4/8 (chosen by the
    batch's distinct-color count: <=4 / <=16 / <=256, and never narrower
    than ``min_bits``), ``palette`` is (4|16|256, C) zero-padded — or
    ``None`` when the tiles hold more than ``max_colors`` distinct
    colors (ship raw instead). Runs as one native C pass when
    available; numpy fallback.
    """
    max_colors = min(int(max_colors), 256)  # uint8 indices; native tables
    b, k, th, tw, c = tiles.shape
    tt = th * tw
    flat = np.ascontiguousarray(tiles).reshape(-1, c)
    out = _palettize_flat(flat, max_colors)
    if out is None:
        return None
    idx, pal, count = out
    if count <= 4 and tt % 4 == 0 and min_bits <= 2:
        pal4c = np.zeros((4, c), np.uint8)
        pal4c[: min(len(pal), 4)] = pal[:4]
        packed = pack_palette_indices(idx, 2).reshape(b, k, tt // 4)
        return packed, pal4c, 2
    if count <= 16 and tt % 2 == 0 and min_bits <= 4:
        pal16 = np.zeros((16, c), np.uint8)
        pal16[: min(len(pal), 16)] = pal[:16]
        packed = pack_palette_indices(idx, 4).reshape(b, k, tt // 2)
        return packed, pal16, 4
    return idx.reshape(b, k, tt), pal, 8


def palettize_frames(frames: np.ndarray, max_colors: int = 256):
    """Try to palette-compress FULL frames (B, H, W, C) — the lossless
    wire+transfer codec for the non-sparse path (no reference frame, no
    temporal assumption; only "synthetic frames carry few colors").

    PER-FRAME palettes: each frame indexes its own color table, so one
    frame's count — not the batch's — picks the index width (a
    flat-shaded frame is typically <=4 colors even when the batch
    drifts past 16). Returns ``(packed, palette, bits)`` — ``packed``
    (B, H*W/4 | H*W/2 | H*W) uint8 for ``bits`` 2/4/8 (16x/8x/4x fewer
    bytes than RGBA across BOTH the socket and the host->device link;
    the device side expands through ``palette`` (B, cap, C),
    :func:`_expand_palette`) — or ``None`` when any single frame holds
    more than ``max_colors`` distinct colors (ship raw instead).
    """
    max_colors = min(int(max_colors), 256)
    b, h, w, c = frames.shape
    hw = h * w
    rows = []
    counts = []
    frames = np.ascontiguousarray(frames)
    for i in range(b):
        out = _palettize_flat(frames[i].reshape(-1, c), max_colors)
        if out is None:
            return None
        idx, pal, count = out
        rows.append((idx, pal))
        counts.append(count)
    cmax = max(counts) if counts else 0
    if cmax <= 4 and hw % 4 == 0:
        bits, cap = 2, 4
    elif cmax <= 16 and hw % 2 == 0:
        bits, cap = 4, 16
    else:
        bits, cap = 8, 256
    palette = np.zeros((b, cap, c), np.uint8)
    packed = np.empty((b, hw * bits // 8), np.uint8)
    for i, (idx, pal) in enumerate(rows):
        palette[i, : counts[i]] = pal[: counts[i]]
        packed[i] = pack_palette_indices(idx, bits)
    return packed, palette, bits


#: Suffixes of the ``tiles.expand_path.*`` trace-time counters
#: :func:`_expand_palette` bumps: how a trace turned palette indices into
#: colours (once per trace, not per execution).
EXPAND_PATHS = ("select", "gather")


def _expand_palette(packed, palette, bits: int):
    """Device-side palette expand of the flattened pixel axis.

    ``packed``: (..., M) uint8 holding ``8/bits`` consecutive pixels a
    byte (first index in the high bits); ``palette``: (cap, C), or
    (..., cap, C) with leading axes matching ``packed``'s leading ones
    (each row then expands through its own palette, one ``vmap`` a
    level). Returns uint8 of ``M * (8/bits) * C`` bytes a row in flat
    pixel-major x channel order — the caller reshapes. Bit-exact by
    construction.

    The palette's row count decides how. 256 rows (``bits == 8``):
    ``palette[packed]``, one look-up a pixel (``expand_path.gather``).
    4 or 16 rows: no indexed load, because a TPU looks the indices of a
    gather up one at a time (5.2 M of them a dispatch took 49 ms) where
    dense arithmetic runs over whole vector registers
    (``expand_path.select``). The flat output is viewed in rows of whole
    128-lane registers (a (16, 32) RGBA tile row is one); a 0/1 product
    spreads each packed byte over the ``(8/bits)*C`` lanes it covers
    (one non-zero term an output and bf16 holds 0..255: exact), a
    per-lane shift and mask cut each lane's index out of its byte, and
    one compare-and-select a palette row picks the colour from the
    palette tiled along the lanes.
    """
    import math

    import jax
    import jax.numpy as jnp

    from blendjax.utils.metrics import metrics

    if palette.ndim >= 3:
        return jax.vmap(
            lambda p, q: _expand_palette(p, q, bits)
        )(packed, palette)
    if bits == 8:
        metrics.count("tiles.expand_path.gather")
        return palette[packed]
    metrics.count("tiles.expand_path.select")
    with jax.named_scope(SCOPE_PALETTE_EXPAND):
        # The operands as they stand in memory: left alone XLA pulls the
        # slices that cut them out of the packed group into the product,
        # and the cube CNN's fused step compiles for a v5e in 30.8 s
        # where it takes 10.2 behind the barrier (the gather's took 8.0).
        packed, palette = jax.lax.optimization_barrier((packed, palette))
        lead, m = packed.shape[:-1], packed.shape[-1]
        cap, c = palette.shape
        px = 8 // bits
        span = px * c  # output bytes (lanes) one packed byte covers
        per_row = math.gcd(m, math.lcm(128, span) // span)  # packed bytes
        lane = np.arange(per_row * span)
        spread = lane // span == np.arange(per_row)[:, None]
        byte = jnp.einsum(
            "...b,bl->...l",
            packed.reshape(*lead, m // per_row, per_row).astype(jnp.bfloat16),
            jnp.asarray(spread, jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        shift = bits * (px - 1 - (lane // c) % px)
        idx = (byte >> jnp.asarray(shift, jnp.int32)) & ((1 << bits) - 1)
        rows = jnp.tile(palette.astype(jnp.int32), (1, lane.size // c))
        out = rows[0]
        for r in range(1, cap):
            out = jnp.where(idx == r, rows[r], out)
        return out.astype(jnp.uint8)


def expand_palette_frames(packed, palette, bits: int, h: int, w: int,
                          c: int):
    """Device-side inverse of :func:`palettize_frames` (jit-safe;
    :func:`_expand_palette`). ``packed``: (..., H*W/4|H*W/2|H*W) uint8;
    ``palette``: (cap, C) batch-level, or (..., cap, C) per-row with
    leading axes matching ``packed``'s (each row expands through its own
    palette). Returns (..., H, W, C) uint8."""
    lead = packed.shape[:-1]
    return _expand_palette(packed, palette, bits).reshape(*lead, h, w, c)


def expand_palette_frames_np(packed, palette, bits: int, h: int, w: int,
                             c: int):
    """Host (numpy) twin of :func:`expand_palette_frames`."""
    if palette.ndim >= 3:
        return np.stack([
            expand_palette_frames_np(p, q, bits, h, w, c)
            for p, q in zip(packed, palette)
        ])
    lead = packed.shape[:-1]
    idx = unpack_palette_indices(packed, bits)
    return palette[idx].reshape(*lead, h, w, c)


def pop_frame_palette_payload(fields: dict, name: str, bits: int, h: int,
                              w: int, c: int, expand):
    """Pop ``name``'s full-frame palette payload from ``fields`` and
    return the expanded frames, where ``expand`` is
    :func:`expand_palette_frames` (device) or
    :func:`expand_palette_frames_np` (host). Shared by every consumer
    (pipeline fast paths, host fallbacks, torch adapter) so the 2/4/8-
    bit wire variants stay in one place."""
    packed = fields.pop(name + FRAMEPAL_SUFFIXES[bits])
    pal = fields.pop(name + PALETTE_SUFFIX)
    return expand(packed, pal, bits, h, w, c)


def pop_frame_palette_batches(hb: dict):
    """Detect+pop full-frame palette batches from a host batch: returns
    ``[(name, (h, w, c, bits))]`` and removes each ``name__frameshape``
    sidecar (the payload/palette fields stay for the decode stage)."""
    out = []
    for key in [k for k in hb if k.endswith(FRAMESHAPE_SUFFIX)]:
        name = key[: -len(FRAMESHAPE_SUFFIX)]
        h, w, c, bits = (int(v) for v in hb.pop(key))
        out.append((name, (h, w, c, bits)))
    return out


def expand_palette_tiles(packed, palette, bits: int, t, c: int):
    """Device-side inverse of :func:`palettize_tiles` (jit-safe;
    :func:`_expand_palette`).

    ``packed``: (..., K, t*t/4|t*t/2|t*t) uint8; ``palette``: (cap, C), or
    (..., cap, C) with leading axes matching ``packed``'s leading dims
    (per-frame palettes, and the chunked-decode case stacks another
    level) — each row then expands through its own palette. ``t`` is an
    int side or ``(th, tw)`` pair. Returns (..., K, th, tw, C) uint8.
    """
    th, tw = tile_hw(t)
    lead = packed.shape[:-1]
    return _expand_palette(packed, palette, bits).reshape(*lead, th, tw, c)


def expand_palette_tiles_np(packed, palette, bits: int, t, c: int):
    """Host (numpy) twin of :func:`expand_palette_tiles`."""
    th, tw = tile_hw(t)
    if palette.ndim >= 3:
        return np.stack([
            expand_palette_tiles_np(p, q, bits, t, c)
            for p, q in zip(packed, palette)
        ])
    lead = packed.shape[:-1]
    idx = unpack_palette_indices(packed, bits)
    return palette[idx].reshape(*lead, th, tw, c)


# -- run-length "ndr" tile-group codec (host encode / device expand) --------
#
# Palette indices (and flat-shaded uint8 frames generally) are run-heavy:
# a background-dominated row is a handful of (value, run) pairs. The
# "ndr" wire kind (blendjax.transport.wire) ships those pairs instead of
# zlib streams, so the consumer either inflates with one vectorized
# np.repeat (still ~10x cheaper than a zlib inflate) or — the fused
# path — defers the expansion to a jitted gather INSIDE the train
# dispatch (:func:`rle_expand_packed`), where it costs zero host time.
#
# Packed per-row layout (one uint8 buffer of shape (rows, cap*(isz+2))):
#   [values: cap x isz bytes][run lo-bytes: cap][run hi-bytes: cap]
# ``isz`` is the run item width in bytes (4 for RGBA pixel runs, 1 for
# palette indices); runs are uint16 split into explicit lo/hi planes so
# host and device decode share one endian-free definition. Unused tail
# entries carry run == 0 and expand to nothing. ``cap`` is the per-row
# pair capacity — sticky per publisher key and bucket-rounded, so the
# packed shape (and with it the consumer's jit cache) stays stable
# across frames, exactly like ``pack_batch``'s tile capacity.

NDR_SUFFIX = "__ndr"          # deferred packed run buffer (rows, stride)
NDRSPEC_SUFFIX = "__ndrspec"  # sidecar [shape, isz, cap] riding the batch

RLE_MAX_RUN = 0xFFFF  # uint16 run length; longer runs split at encode
RLE_BUCKET = 64       # cap rounding granularity (jit-cache stability)


def rle_item_size(shape) -> int:
    """Run item width in bytes for a uint8 array ``shape``: the trailing
    channel dim when it looks like pixels ((..., C) with C <= 4), else
    single bytes. One definition shared by encoder and decoder."""
    if len(shape) >= 2 and 2 <= int(shape[-1]) <= 4:
        return int(shape[-1])
    return 1


def rle_packed_stride(cap: int, isz: int) -> int:
    return int(cap) * (int(isz) + 2)


def _rle_geometry(shape, isz: int):
    """shape -> (rows, items-per-row). Rows are the leading axis (the
    batch of a batched field, scan lines of a single frame)."""
    shape = tuple(int(s) for s in shape)
    total = 1
    for s in shape:
        total *= s
    rows = shape[0] if len(shape) >= 2 else 1
    if rows <= 0 or total <= 0:
        raise ValueError(f"ndr geometry needs a non-empty shape, got {shape}")
    row_bytes, rem = divmod(total, rows)
    if rem or row_bytes % isz:
        raise ValueError(
            f"ndr geometry {shape} does not split into rows of whole "
            f"{isz}-byte items"
        )
    return rows, row_bytes // isz


def rle_encode_rows(arr: np.ndarray, cap: int | None = None,
                    bucket: int = RLE_BUCKET):
    """Run-length encode a uint8 array row-wise into the packed wire
    layout. Returns ``(buf (rows, cap*(isz+2)) uint8, cap, isz)`` or
    ``None`` when the array is ineligible (non-uint8, empty) or does
    not fit: a pinned ``cap`` too small for this frame's run count
    (caller falls back to raw — the per-key skip memo in
    ``blendjax.transport.wire`` keeps that cheap)."""
    if not isinstance(arr, np.ndarray) or arr.dtype != np.uint8 or arr.size == 0:
        return None
    isz = rle_item_size(arr.shape)
    try:
        rows, t = _rle_geometry(arr.shape, isz)
    except ValueError:
        isz = 1
        rows, t = _rle_geometry(arr.shape, isz)
    flat = np.ascontiguousarray(arr).reshape(rows, t, isz)
    per = []
    kmax = 1
    for r in range(rows):
        row = flat[r]
        change = np.empty(t, np.bool_)
        change[0] = True
        if t > 1:
            np.any(row[1:] != row[:-1], axis=1, out=change[1:])
        starts = np.flatnonzero(change)
        runs = np.diff(np.append(starts, t)).astype(np.int64)
        if len(runs) and runs.max() > RLE_MAX_RUN:
            reps = (runs + RLE_MAX_RUN - 1) // RLE_MAX_RUN
            vals = np.repeat(row[starts], reps, axis=0)
            split = np.full(int(reps.sum()), RLE_MAX_RUN, np.int64)
            split[np.cumsum(reps) - 1] = runs - (reps - 1) * RLE_MAX_RUN
            runs = split
        else:
            vals = row[starts]
        kmax = max(kmax, len(runs))
        per.append((vals, runs))
    if cap is not None:
        if kmax > int(cap):
            return None
        cap = int(cap)
    else:
        bucket = max(int(bucket), 1)
        cap = max(-(-kmax // bucket) * bucket, bucket)
    buf = np.zeros((rows, rle_packed_stride(cap, isz)), np.uint8)
    vals_plane = buf[:, : cap * isz].reshape(rows, cap, isz)
    lo_plane = buf[:, cap * isz: cap * (isz + 1)]
    hi_plane = buf[:, cap * (isz + 1):]
    for r, (vals, runs) in enumerate(per):
        k = len(runs)
        vals_plane[r, :k] = vals
        lo_plane[r, :k] = (runs & 0xFF).astype(np.uint8)
        hi_plane[r, :k] = (runs >> 8).astype(np.uint8)
    return buf, cap, isz


def _rle_runs_np(buf: np.ndarray, cap: int, isz: int):
    vals = buf[:, : cap * isz].reshape(buf.shape[0], cap, isz)
    lo = buf[:, cap * isz: cap * (isz + 1)].astype(np.uint32)
    hi = buf[:, cap * (isz + 1):].astype(np.uint32)
    return vals, lo | (hi << 8)


def rle_validate_packed(buf, shape, isz: int, cap: int) -> None:
    """Hostile-stream guards for a packed run buffer — the ndz decode
    bounds carried over to the DEFERRED device plan: allocation is
    bounded by the declared shape, the buffer must carry exactly the
    declared capacity, and each row's runs must sum to the declared
    item count (truncated or padded streams fail loudly here instead of
    expanding to garbage inside the train jit). Cheap: reads only the
    2*cap run bytes per row, never the values."""
    isz, cap = int(isz), int(cap)
    if isz < 1 or isz > 16 or cap < 1:
        raise ValueError(f"ndr spec out of bounds (isz={isz}, cap={cap})")
    rows, t = _rle_geometry(shape, isz)  # raises on zero-byte shapes
    buf = np.asarray(buf)
    if buf.dtype != np.uint8 or buf.shape != (rows, rle_packed_stride(cap, isz)):
        raise ValueError(
            f"ndr buffer shape {buf.shape}/{buf.dtype} does not match "
            f"declared rows={rows} cap={cap} isz={isz}"
        )
    _, runs = _rle_runs_np(buf, cap, isz)
    sums = runs.sum(axis=1)
    if not (sums == t).all():
        raise ValueError(
            f"ndr rows do not expand to the declared {t} items "
            f"(row sums {sums.min()}..{sums.max()})"
        )


def rle_expand_packed_np(buf: np.ndarray, shape, isz: int, cap: int):
    """Host (numpy) inverse of :func:`rle_encode_rows` — what the wire
    decode uses when the consumer does not defer to device. Validates
    first (same guards as the deferred plan)."""
    rle_validate_packed(buf, shape, isz, cap)
    shape = tuple(int(s) for s in shape)
    rows, _t = _rle_geometry(shape, int(isz))
    vals, runs = _rle_runs_np(np.asarray(buf), int(cap), int(isz))
    out = np.concatenate(
        [np.repeat(vals[r], runs[r], axis=0) for r in range(rows)]
    )
    return out.reshape(shape)


def rle_expand_packed(buf, shape, isz: int, cap: int):
    """Device-side (jit-safe) inverse of :func:`rle_encode_rows`: one
    ``cumsum`` over the run planes plus one ``searchsorted`` gather per
    row — the scan/gather that lets ``make_fused_tile_step`` decompress
    the wire INSIDE the train dispatch with zero host inflate cost.
    Static shapes come from the decode plan; a hostile buffer that
    slipped past host validation can only produce wrong pixels, never
    out-of-bounds memory (indices clamp to ``cap``)."""
    import jax
    import jax.numpy as jnp

    shape = tuple(int(s) for s in shape)
    isz, cap = int(isz), int(cap)
    rows, t = _rle_geometry(shape, isz)
    buf = buf.reshape(rows, rle_packed_stride(cap, isz))
    vals = buf[:, : cap * isz].reshape(rows, cap, isz)
    lo = buf[:, cap * isz: cap * (isz + 1)].astype(jnp.uint32)
    hi = buf[:, cap * (isz + 1):].astype(jnp.uint32)
    ends = jnp.cumsum(lo | (hi << 8), axis=1)
    pos = jnp.arange(t, dtype=jnp.uint32)
    idx = jax.vmap(
        lambda e: jnp.searchsorted(e, pos, side="right")
    )(ends)
    out = jax.vmap(lambda v, i: v[jnp.minimum(i, cap - 1)])(vals, idx)
    return out.reshape(shape)


def pop_rle_batches(fields: dict):
    """Detect+pop deferred run-length sidecars from a host batch:
    returns the static plan ``((base, (shape, isz, cap)), ...)`` and
    removes each ``<base>__ndrspec`` entry (the ``<base>__ndr`` buffer
    stays for packing/transfer). The shared bookkeeping for every
    consumer of deferred "ndr" wire frames."""
    out = []
    for key in [k for k in fields if k.endswith(NDRSPEC_SUFFIX)]:
        base = key[: -len(NDRSPEC_SUFFIX)]
        shape, isz, cap = fields.pop(key)
        out.append((base, (tuple(int(s) for s in shape), int(isz), int(cap))))
    return tuple(out)


def expand_rle_fields(fields: dict, rle_groups) -> dict:
    """Expand every deferred run buffer of an (unpacked, on-device)
    field dict in place — jit-safe; runs FIRST in the decode entry
    points below so palette/tile expansion sees the restored fields."""
    for base, (shape, isz, cap) in rle_groups:
        fields[base] = rle_expand_packed(
            fields.pop(base + NDR_SUFFIX), shape, isz, cap
        )
    return fields


# -- packed single-transfer form --------------------------------------------
#
# pack_fields/unpack_fields collapse a batch dict (idx, tiles, labels,
# ids, ...) into ONE uint8 buffer + a static spec; the unpack runs under
# jit on device (slice + bitcast), so the whole batch rides a single
# device_put.


# 64-bit payloads are value-cast to 32 bits on the host before packing —
# the same width jax's dtype canonicalization would give them on
# device_put (and, for floats, a correct numeric conversion where a raw
# bitcast would silently produce garbage). Skipped entirely when
# jax_enable_x64 is set (device_put would keep 64 bits then, and the
# packed path must match the raw-frame path bit for bit). Integer
# narrowing is range-checked: a value that doesn't fit 32 bits (e.g. a
# time_ns timestamp) raises instead of silently wrapping.
_PACK_NARROW = {
    np.dtype(np.float64): np.float32,
    np.dtype(np.int64): np.int32,
    np.dtype(np.uint64): np.uint32,
}


def _narrow_for_pack(name: str, arr: np.ndarray) -> np.ndarray:
    import jax

    if jax.config.jax_enable_x64:
        return arr  # device keeps 64 bits; pack must too
    target = _PACK_NARROW[arr.dtype]
    if arr.dtype.kind in "iu" and arr.size:
        info = np.iinfo(target)
        lo, hi = int(arr.min()), int(arr.max())
        if lo < info.min or hi > info.max:
            raise ValueError(
                f"pack_fields: field {name!r} ({arr.dtype}) holds values "
                f"[{lo}, {hi}] that do not fit {np.dtype(target)} — "
                "pre-cast the field on the producer (e.g. ms instead of "
                "time_ns) or enable jax_enable_x64"
            )
    return arr.astype(target)


def pack_fields(fields: dict):
    """Concatenate ndarray fields into one uint8 buffer.

    Returns ``(buf uint8[total], spec)`` where ``spec`` is a hashable
    tuple of ``(name, dtype_str, shape, offset, nbytes)`` suitable as a
    static jit argument for :func:`unpack_fields`. 64-bit fields are
    narrowed to 32 bits first (see ``_PACK_NARROW``) and bools travel as
    bytes, so every packed dtype reconstructs exactly on device.
    """
    spec = []
    offset = 0
    parts = []
    for name, arr in fields.items():
        arr = np.ascontiguousarray(arr)
        if arr.dtype in _PACK_NARROW:
            arr = _narrow_for_pack(name, arr)
        raw = arr.view(np.uint8).reshape(-1)
        spec.append((name, arr.dtype.str, arr.shape, offset, raw.nbytes))
        parts.append(raw)
        offset += raw.nbytes
    return np.concatenate(parts), tuple(
        (n, d, tuple(int(x) for x in s), o, b) for n, d, s, o, b in spec
    )


def unpack_fields(buf, spec):
    """Device-side inverse of :func:`pack_fields` (jit-safe: slices +
    ``lax.bitcast_convert_type``). ``buf`` is the transferred uint8
    buffer; returns ``{name: array}``."""
    from jax import lax

    out = {}
    for name, dtype_str, shape, offset, nbytes in spec:
        dt = np.dtype(dtype_str)
        raw = lax.dynamic_slice_in_dim(buf, offset, nbytes)
        if dt == np.uint8:
            arr = raw
        elif dt == np.bool_:
            arr = raw.astype(np.bool_)  # packed as 0/1 bytes
        elif dt.itemsize == 1:
            arr = lax.bitcast_convert_type(raw, dt)
        else:
            arr = lax.bitcast_convert_type(raw.reshape(-1, dt.itemsize), dt)
        out[name] = arr.reshape(shape)
    return out


def decode_packed_superbatch(packed, refs, spec, names, geoms,
                             mesh=None, data_axis: str = "data",
                             rle_groups=()):
    """Decode a stacked packed chunk group to full fields — jit-safe.

    ``packed``: (K, total) uint8, K packed batches of identical layout
    ``spec``. Each image field in ``names`` is reconstructed against its
    device reference ``refs[name]`` with the per-name geometry in
    ``geoms``; every name's tiles decode flattened over (K*B) in ONE
    scatter call. Returns ``{field: (K, B, ...)}`` — all sidecar fields
    keep their packed (K, ...) shapes.

    Shared by :class:`blendjax.data.TileStreamDecoder` (decode-then-step)
    and :func:`blendjax.train.make_fused_tile_step` (decode fused into
    the train jit: one device call per K batches instead of two).
    """
    import jax

    fields = jax.vmap(
        lambda p: expand_rle_fields(unpack_fields(p, spec), rle_groups)
    )(packed)
    for name, geom in zip(names, geoms):
        idx = fields.pop(name + TILEIDX_SUFFIX)
        tiles = pop_tile_payload(fields, name, geom, expand_palette_tiles)
        k, b = idx.shape[:2]
        img = decode_tile_delta(
            refs[name],
            idx.reshape(k * b, *idx.shape[2:]),
            tiles.reshape(k * b, *tiles.shape[2:]),
            geom[:3],
            mesh=mesh, data_axis=data_axis,
        )
        fields[name] = img.reshape(k, b, *img.shape[1:])
    return fields


def decode_packed_pal_batch(packed, spec, pal_groups, rle_groups=()):
    """Decode ONE packed full-frame-palette batch to full fields —
    jit-safe (slice/bitcast unpack + :func:`_expand_palette`).

    ``packed``: (total,) uint8 buffer of :func:`pack_fields` layout
    ``spec``; ``pal_groups``: ``((name, (h, w, c, bits)), ...)`` as
    produced by :func:`pop_frame_palette_batches`; ``rle_groups``: the
    deferred run-length plan from :func:`pop_rle_batches`, expanded
    first (a palette index plane may itself ride the wire run-packed,
    and a raw uint8 frame may ride with ``pal_groups`` empty). Shared
    by :class:`blendjax.data.TileStreamDecoder` (decode-then-step) and
    :func:`blendjax.train.make_fused_tile_step` (decode fused into the
    train jit), so the two paths cannot drift."""
    fields = expand_rle_fields(unpack_fields(packed, spec), rle_groups)
    for name, (h, w, c, bits) in pal_groups:
        fields[name] = pop_frame_palette_payload(
            fields, name, bits, h, w, c, expand_palette_frames
        )
    return fields


def decode_packed_pal_superbatch(packed, spec, pal_groups, rle_groups=()):
    """(K', total) stacked packed pal buffers -> (K', B, ...) superbatch
    fields — each group member expands through its OWN palette (vmap
    over the chunk axis). The full-frame-palette twin of
    :func:`decode_packed_superbatch`, consumed by the same two callers.
    """
    import jax

    return jax.vmap(
        lambda p: decode_packed_pal_batch(p, spec, pal_groups, rle_groups)
    )(packed)


# -- device side ------------------------------------------------------------


def tile_ref(ref, tile=TILE):
    """Reference image (H, W, C) -> device-resident tiled view
    (num_tiles, th, tw, C); compute once per stream, reuse per batch."""
    import jax.numpy as jnp

    ref = jnp.asarray(ref)
    h, w, c = ref.shape
    th, tw = tile_hw(tile)
    gh, gw = tile_grid(ref.shape, (th, tw))
    return ref.reshape(gh, th, gw, tw, c).transpose(0, 2, 1, 3, 4).reshape(
        gh * gw, th, tw, c
    )


def tile_ref_np(ref: np.ndarray, tile=TILE) -> np.ndarray:
    """Host (numpy) twin of :func:`tile_ref` — for consumers that must
    assemble the tiled reference into a multi-process global array
    (``jax.make_array_from_process_local_data`` takes host data)."""
    h, w, c = ref.shape
    th, tw = tile_hw(tile)
    gh, gw = tile_grid(ref.shape, (th, tw))
    return np.ascontiguousarray(
        ref.reshape(gh, th, gw, tw, c)
        .transpose(0, 2, 1, 3, 4)
        .reshape(gh * gw, th, tw, c)
    )


def _pallas_decode_scatter(ref_tiles, idx, tiles, interpret: bool = False):
    """Pallas TPU kernel for the tile scatter: ``(B, N, t*t*C)`` output
    where each grid step (b, k) DMAs one changed tile into the slot
    ``idx[b, k]`` of a reference-initialized buffer.

    The TPU-idiomatic form of a sparse update (pallas_guide.md
    "PrefetchScalarGridSpec"): ``idx`` rides as a scalar-prefetch operand
    so the *output* BlockSpec's index_map is data-dependent — the kernel
    body is a single VMEM block copy, and sentinel indices land in a
    padded slot ``N`` that the caller slices off. The reference-broadcast
    base is donated via ``input_output_aliases`` so unwritten slots keep
    their contents.

    Returns (B, N, t*t*C) uint8 (flattened tiles; caller reshapes).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, k = idx.shape
    n = ref_tiles.shape[0]
    th, tw, c = tiles.shape[-3], tiles.shape[-2], tiles.shape[-1]
    ttc = th * tw * c
    # Each tile is viewed as an (8, ttc/8) block: Mosaic's lowering check
    # requires the trailing two block dims be divisible by (8, 128), and
    # every RGBA tile size is a multiple of 1024 bytes (16*16*4), so
    # ttc/8 is a multiple of 128. (uint8's native tile is (32, 128) —
    # the compiler pads the sublane dim; measured ~25x faster than the
    # XLA scatter on a v5e chip regardless, since the op is one DMA per
    # tile. Covered on real hardware by the tpu-marked test.)
    lanes = ttc // 8
    base = jnp.broadcast_to(
        ref_tiles.reshape(1, n, 8, lanes), (b, n, 8, lanes)
    )
    # One sentinel slot at N absorbs padding writes.
    basep = jnp.concatenate(
        [base, jnp.zeros((b, 1, 8, lanes), jnp.uint8)], axis=1
    )
    flat_tiles = tiles.reshape(b, k, 8, lanes)

    def kernel(idx_ref, base_ref, tiles_blk, out_blk):
        del idx_ref, base_ref  # consumed by the out index_map / aliasing
        out_blk[...] = tiles_blk[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # base: alias target
            pl.BlockSpec(
                (1, 1, 8, lanes), lambda bi, ki, idxp: (bi, ki, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 8, lanes),
            lambda bi, ki, idxp: (bi, idxp[bi, ki], 0, 0),
        ),
    )
    # the kernel's name, as the call's ``name=`` and as a scope: the
    # scope puts it on the name stack of the operation in a trace
    with jax.named_scope(KERNEL_TILE_DECODE_SCATTER):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n + 1, 8, lanes), jnp.uint8),
            input_output_aliases={1: 0},  # basep (after the prefetch arg)
            interpret=interpret,
            name=KERNEL_TILE_DECODE_SCATTER,
        )(idx, basep, flat_tiles)
    return out[:, :n].reshape(b, n, ttc)


def _pallas_decode_spatial(ref_tiles, idx, tiles, shape,
                           interpret: bool = False):
    """Direct-spatial Pallas decode: ONE kernel pass writes the full
    frames in frame layout. Each grid step owns one tile footprint of
    the output and gathers either the changed tile that landed there or
    the reference block — so the slot buffer, its reference-broadcast
    init pass, and the tile->frame transpose pass of
    :func:`_pallas_decode_scatter` all disappear.

    The tile->slot map inverts on device first (one tiny scatter over
    (B, GH*GW) int32): ``inv[b, p]`` is the row of ``tiles`` covering
    slot ``p``, or K for "unchanged". The kernel's tile-input index_map
    then reads ``inv`` as a scalar-prefetch operand (gather form — the
    data-dependent BlockSpec pattern of pallas_guide.md), and the body
    selects tile vs reference on ``inv < K``.

    Needs ``tw*C % 128 == 0`` (a tile row spans whole 128-lane vregs —
    why rectangular (16, 32) tiles exist for C=4) and ``th % 8 == 0``;
    callers gate on that. ``idx``: (B, K) int32 with sentinel N (those
    rows land in a dropped pad slot of ``inv``). Returns (B, H, W, C)
    uint8, bit-exact.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, k = idx.shape
    th, tw, c = tiles.shape[-3], tiles.shape[-2], tiles.shape[-1]
    h, w, _ = (int(s) for s in shape)
    gh, gw = h // th, w // tw
    n = gh * gw
    twc = tw * c
    ref_img = ref_tiles.reshape(gh, gw, th, tw, c).transpose(
        0, 2, 1, 3, 4
    ).reshape(h, w * c)  # ~1 MB un-tiling; noise next to the frame write
    if k == 0:  # nothing changed anywhere: every block is the reference
        return jnp.broadcast_to(
            ref_img.reshape(1, h, w, c), (b, h, w, c)
        )
    inv = jnp.full((b, n + 1), k, jnp.int32)
    inv = inv.at[
        jnp.arange(b, dtype=jnp.int32)[:, None], idx
    ].set(
        jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None, :], (b, k)),
        mode="drop",
    )[:, :n]
    tiles3 = tiles.reshape(b, k, th, twc)

    def kernel(inv_ref, ref_blk, tile_blk, out_blk):
        bi = pl.program_id(0)
        gy = pl.program_id(1)
        gx = pl.program_id(2)
        j = inv_ref[bi, gy * gw + gx]
        out_blk[0] = jnp.where(j < k, tile_blk[0, 0], ref_blk[...])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, gh, gw),
        in_specs=[
            pl.BlockSpec((th, twc), lambda bi, gy, gx, invp: (gy, gx)),
            # Unchanged blocks clamp to a real (ignored) tile row so the
            # index stays in bounds without a padded tile copy.
            pl.BlockSpec(
                (1, 1, th, twc),
                lambda bi, gy, gx, invp: (
                    bi,
                    jnp.minimum(invp[bi, gy * gw + gx], k - 1),
                    0, 0,
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, th, twc), lambda bi, gy, gx, invp: (bi, gy, gx)
        ),
    )
    with jax.named_scope(KERNEL_TILE_DECODE_SPATIAL):  # as in the scatter
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, w * c), jnp.uint8),
            interpret=interpret,
            name=KERNEL_TILE_DECODE_SPATIAL,
        )(inv, ref_img, tiles3)
    return out.reshape(b, h, w, c)


#: Suffixes of the ``tiles.decode_path.*`` trace-time counters
#: :func:`decode_tile_delta` bumps: which implementation a trace took,
#: plus ``shard_map`` when the kernel was wrapped for a multi-device mesh.
DECODE_PATHS = ("pallas_spatial", "pallas_scatter", "xla_scatter", "shard_map")


def decode_tile_delta(ref_tiles, idx, tiles, shape, use_pallas=None,
                      mesh=None, data_axis: str = "data"):
    """Reconstruct exact full frames on device.

    ``ref_tiles``: (N, t, t, C) from :func:`tile_ref` (any backend array).
    ``idx``: (B, K) int32 flattened tile indices, sentinel ``N`` = no-op.
    ``tiles``: (B, K, t, t, Ct) changed tile contents. ``Ct < C`` means the
    producer shipped only the leading channels (e.g. RGB of an RGBA stream
    whose alpha matched the reference everywhere — it verified that before
    slicing); the remaining channels reconstruct from the reference. Still
    bit-exact.
    ``shape``: static (H, W, C) of the full image.

    Returns (B, H, W, C). Jit-safe (static shapes; the sentinel rides on
    scatter ``mode='drop'``), batch-parallel (``vmap`` over B, so a batch
    sharded along ``data`` decodes shard-locally with a replicated ref).

    ``use_pallas=None`` auto-selects a Pallas kernel on TPU: the
    direct-spatial gather (:func:`_pallas_decode_spatial` — one pass,
    no slot buffer, no transpose) when the tile geometry is
    lane-aligned (``tw*C % 128 == 0``, ``th % 8 == 0``; the (16, 32)
    tiles the flagship scene streams), else the slot scatter
    (:func:`_pallas_decode_scatter`). Channel-sliced tiles (``Ct < C``,
    e.g. alpha slicing) stay kernel-eligible: the missing channels are
    restored from the reference by one on-device gather first.

    ``mesh`` says where the program runs. ``None`` (or a one-device
    mesh) means a single-device program and the kernel is called bare,
    however many devices the host has; a partitioned program that
    reaches it that way is refused at lowering, not rerouted. A
    multi-device ``mesh`` wraps the kernel in ``shard_map`` (the kernel
    alone is not GSPMD-partitionable): over ``data_axis`` when the mesh
    has it — each device decodes its local batch shard against the
    replicated reference — and replicated otherwise. Only when B does
    not divide by the axis size does auto-select take the vmap'd XLA
    scatter, which partitions like any other op. Off TPU the kernels
    run in interpreter mode (what the virtual-mesh tests use).

    The path traced is counted under ``tiles.decode_path.<path>``
    (:data:`DECODE_PATHS`; once per trace, not per execution).
    """
    import jax

    from blendjax.utils.metrics import metrics

    h, w, c = (int(s) for s in shape)
    th, tw = tiles.shape[-3], tiles.shape[-2]
    ct = tiles.shape[-1]
    gh, gw = tile_grid((h, w, c), (th, tw))
    b = idx.shape[0]
    multi_device = mesh is not None and mesh.size > 1
    n_axis = (
        int(mesh.shape[data_axis])
        if multi_device and data_axis in mesh.shape else 1
    )
    eligible_spatial = (tw * c) % 128 == 0 and th % 8 == 0
    eligible = eligible_spatial or (th * tw * c) % 1024 == 0
    if use_pallas is None:
        use_pallas = (
            jax.default_backend() == "tpu"
            and eligible
            and b % n_axis == 0
        )
    if use_pallas and not eligible:
        # explicit request for a kernel that can't lower: fail loudly
        # rather than silently measuring/testing the XLA path
        raise ValueError(
            f"use_pallas=True but tile geometry {th}x{tw}x{c} is not "
            "kernel-eligible (needs tw*C % 128 == 0 and th % 8 == 0, "
            "or th*tw*C % 1024 == 0)"
        )
    if use_pallas:
        interpret = jax.default_backend() != "tpu"
        kernel = "pallas_spatial" if eligible_spatial else "pallas_scatter"
        metrics.count(f"tiles.decode_path.{kernel}")

        if ct < c:
            # Channel-sliced stream (e.g. alpha slicing): the producer
            # verified the trailing channels match the reference on
            # every changed tile, so restore them ON DEVICE from the
            # reference with one small gather — the stream then rides
            # the kernel path instead of silently dropping to the XLA
            # scatter (sentinel rows clamp to a real tile; their
            # content lands in the dropped slot either way).
            import jax.numpy as jnp

            rest = ref_tiles[..., ct:]  # (N, th, tw, C-Ct)
            filled = rest[jnp.minimum(idx, gh * gw - 1)]
            tiles = jnp.concatenate([tiles, filled], axis=-1)

        if eligible_spatial:
            def decode_fn(r, i, tl):
                return _pallas_decode_spatial(
                    r, i, tl, (h, w, c), interpret=interpret
                )
        else:
            def decode_fn(r, i, tl):
                return _pallas_decode_scatter(
                    r, i, tl, interpret=interpret
                ).reshape(-1, gh, gw, th, tw, c).transpose(
                    0, 1, 3, 2, 4, 5
                ).reshape(-1, h, w, c)

        if multi_device:
            from jax.sharding import PartitionSpec as P

            from blendjax.parallel.collectives import _shard_map

            batch_spec = P(data_axis) if n_axis > 1 else P()
            # check=False: pallas_call's out_shape carries no varying-
            # mesh-axes annotation, which the VMA checker requires.
            decode_fn = _shard_map(
                decode_fn, mesh,
                in_specs=(P(), batch_spec, batch_spec),
                out_specs=batch_spec,
                check=False,
            )
            metrics.count("tiles.decode_path.shard_map")
        return decode_fn(ref_tiles, idx, tiles)

    metrics.count("tiles.decode_path.xla_scatter")

    def one(i, tl):
        if ct < c:
            return ref_tiles.at[i, :, :, :ct].set(tl, mode="drop")
        return ref_tiles.at[i].set(tl, mode="drop")

    out = jax.vmap(one)(idx, tiles)  # (B, N, th, tw, C)
    return out.reshape(b, gh, gw, th, tw, c).transpose(0, 1, 3, 2, 4, 5).reshape(
        b, h, w, c
    )
