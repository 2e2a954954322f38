"""On-demand g++ build + ctypes loading of the native accelerators.

No pybind11 in this environment, so the ABI is plain C (``extern "C"``)
over ctypes. The shared object is cached next to the package keyed by a
source hash, so rebuilds happen only when the source changes. Set
``BLENDJAX_NO_NATIVE=1`` to force the Python fallbacks.

A failed build falls back to Python (Blender's bundled interpreter may
have no compiler beside it) an order of magnitude slower, so which one
loaded is never left to a log line: :func:`native_status` answers for
this process, and the ``native.loaded`` / ``native.fallbacks`` counters
ride the producers' telemetry to the consumer.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading

from blendjax.utils.logging import get_logger
from blendjax.utils.metrics import metrics

logger = get_logger("native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_CACHE: dict = {}


def _build(src_path: str, tag: str):
    with open(src_path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_HERE, f"_{tag}_{digest}.so")
    if not os.path.exists(out):
        tmp = tempfile.mktemp(suffix=".so", dir=_HERE)
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
            "-o", tmp, src_path,
        ]
        try:
            subprocess.run(
                cmd, check=True, capture_output=True, timeout=120
            )
            os.replace(tmp, out)  # atomic: safe across concurrent builds
        except (OSError, subprocess.SubprocessError) as e:
            stderr = getattr(e, "stderr", b"") or b""
            logger.warning(
                "native build of %s failed (%s) %s; using Python fallback",
                tag, e, stderr.decode(errors="replace")[:500],
            )
            if os.path.exists(tmp):
                os.remove(tmp)
            return None
    return ctypes.CDLL(out)


def _load(name: str, tag: str, symbol: str, restype, argtypes):
    """Resolve entry point ``symbol`` of ``<tag>.cpp`` once per process:
    the bound ctypes function, or None for the Python fallback. Counts
    the outcome."""
    if os.environ.get("BLENDJAX_NO_NATIVE") == "1":
        return None
    with _LOCK:
        if name not in _CACHE:
            lib = _build(os.path.join(_HERE, tag + ".cpp"), tag)
            fn = None
            if lib is not None:
                fn = getattr(lib, symbol)
                fn.restype = restype
                fn.argtypes = argtypes
            metrics.count("native.loaded" if fn else "native.fallbacks")
            _CACHE[name] = fn
        return _CACHE[name]


def native_status() -> dict:
    """``{entry point: True (native) | False (Python fallback)}`` for
    every entry point this process has resolved so far."""
    with _LOCK:
        return {name: fn is not None for name, fn in _CACHE.items()}


_U8P = ctypes.POINTER(ctypes.c_uint8)
_I64 = ctypes.c_int64
_PTR = ctypes.c_void_p


def load_tile_delta():
    """Returns the native changed-tile scan or None.

    ``tile_delta(img u8[h,w,c], ref u8[h,w,c], h, w, c, th, tw, ty0,
    ty1, tx0, tx1, idx_out i32[n_tiles], tiles_out u8[n_tiles,th,tw,c])
    -> count`` (tile-grid bounds restrict the scan; th/tw are the tile
    pixel dims — square tiles pass the same value twice).
    """
    return _load(
        "tiledelta", "tiledelta", "bjx_tile_delta",
        _I64,
        [_U8P, _U8P] + [_I64] * 9 + [ctypes.POINTER(ctypes.c_int32), _U8P],
    )


def load_palettize():
    """Returns the native palette-build pass or None.

    ``palettize(px u8[n,c], n, c, cap, palette_out u8[cap,c],
    idx_out u8[n]) -> count | -1``.
    """
    return _load(
        "palettize", "tiledelta", "bjx_palettize",
        _I64, [_U8P, _I64, _I64, _I64, _U8P, _U8P],
    )


def load_tile_delta_palidx():
    """Returns the fused changed-tile scan + palettizer or None.

    ``tile_delta_palidx(img, ref, h, w, c, th, tw, ty0, ty1, tx0, tx1,
    idx_out i32[n_tiles], palidx_out u8[n_tiles*th*tw], keys u32[1024],
    vals i16[1024], palette u8[256*c], pcount i64[1], cap) ->
    count | -1`` — keys/vals/palette/pcount are caller-owned persistent
    stream state.
    """
    # void* buffer args: callers pass cached raw addresses (ints) instead
    # of re-marshalling POINTER objects per frame — this is the
    # producer's per-frame hot call.
    return _load(
        "tiledelta_palidx", "tiledelta",
        "bjx_tile_delta_palidx", _I64,
        [_PTR, _PTR] + [_I64] * 9 + [_PTR] * 6 + [_I64],
    )


def load_render_frame():
    """Returns the one-call frame renderer or None.

    ``render_frame(verts f64[n,3,3], rgba u8[n,4], n, light f64[3],
    view f64[4,4], proj f64[4,4], clip_near, color u8[h,w,4],
    zbuf f32[h,w], h, w, bg u8[4], prev_rect i64[4], out_rect i64[4])``
    — projection + flat shading + near cull + dirty-rect clear + fill in
    one FFI crossing (the producer's per-frame hot call; buffer args are
    ``c_void_p`` so callers can pass cached raw addresses).
    """
    return _load(
        "render_frame", "rasterizer", "bjx_render_frame",
        None,
        [_PTR, _PTR, _I64, _PTR, _PTR, _PTR, ctypes.c_double, _PTR, _PTR,
         _I64, _I64, _PTR, _PTR, _PTR],
    )
