"""Native (C++) accelerators with pure-Python fallbacks.

The reference has no native code of its own but leans on native wheels for
its hot paths (SURVEY.md §2: libzmq, PyOpenGL readback, torch); blendjax's
native layer covers the pieces those wheels don't: the producer-side
rasterizer fill loop and the tile-delta changed-tile scan. Built on demand
with g++ (see ``build.py``); every caller must work when the toolchain is
absent.
"""

from blendjax._native.build import (
    load_palettize,
    load_render_frame,
    load_tile_delta,
    load_tile_delta_palidx,
    native_status,
)

__all__ = [
    "load_render_frame",
    "load_tile_delta",
    "load_palettize",
    "load_tile_delta_palidx",
    "native_status",
]
