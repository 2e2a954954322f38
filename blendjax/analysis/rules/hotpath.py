"""BJX102 host-sync-in-hot-path: device sync inside the streaming loop.

The streaming modules (``blendjax/data/pipeline.py``,
``blendjax/data/batcher.py``) exist to keep host->device transfer
asynchronous and overlapped with compute; one stray
``block_until_ready()``, ``.item()``, or host cast of a device array
serializes the whole ring. Modules opt in with a
``bjx: hot-path`` marker comment; the two streaming modules are always
hot by basename.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterator

from blendjax.analysis.core import (
    Finding,
    ModuleContext,
    Rule,
    register,
    walk_shallow,
)

HOT_BASENAMES = {"pipeline.py", "batcher.py"}
# Comment lines only: the marker quoted in a docstring (this module's
# own, say) must not opt a module in.
HOT_MARKER_RE = re.compile(r"^\s*#.*bjx: hot-path", re.MULTILINE)

# jax placement calls whose results are device arrays: host casts of
# names bound to these are definite device->host syncs.
PLACEMENT_CALLS = {"device_put", "make_array_from_process_local_data"}
HOST_CASTS = {"float", "int", "bool"}
HOST_ARRAY_CASTS = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray"}


def _is_hot(module: ModuleContext) -> bool:
    if os.path.basename(module.relpath) in HOT_BASENAMES:
        return True
    return HOT_MARKER_RE.search(module.source[:4096]) is not None


@register
class HostSyncRule(Rule):
    id = "BJX102"
    name = "host-sync-in-hot-path"
    description = (
        "blocking device synchronization (block_until_ready/.item()/host "
        "cast of a placed array) inside a streaming hot-path module"
    )

    def check(self, module: ModuleContext) -> Iterator[Finding]:
        if not _is_hot(module):
            return
        for qual, fn, _cls in module.iter_functions():
            yield from self._scan(module, fn, qual)

    def _scan(
        self, module: ModuleContext, fn: ast.AST, qual: str
    ) -> Iterator[Finding]:
        # Names bound (anywhere in this function) to a jax placement call:
        # host-casting those is a guaranteed device->host round trip.
        placed: set[str] = set()
        for node in walk_shallow(fn):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
            ):
                resolved = module.resolve(node.value.func) or ""
                if resolved.rsplit(".", 1)[-1] in PLACEMENT_CALLS:
                    placed.add(node.targets[0].id)

        for node in walk_shallow(fn):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr == "block_until_ready":
                yield self.finding(
                    module,
                    node,
                    f"block_until_ready() in hot-path '{qual}' stalls the "
                    "transfer ring (prefetch/throttle should bound the "
                    "queue instead)",
                )
                continue
            resolved = module.resolve(func) or ""
            if resolved.endswith(".block_until_ready"):
                yield self.finding(
                    module,
                    node,
                    f"jax.block_until_ready() in hot-path '{qual}' stalls "
                    "the transfer ring (prefetch/throttle should bound the "
                    "queue instead)",
                )
                continue
            if (
                isinstance(func, ast.Attribute)
                and func.attr == "item"
                and not node.args
            ):
                yield self.finding(
                    module,
                    node,
                    f".item() in hot-path '{qual}' forces a device->host "
                    "transfer per element (keep reductions on device)",
                )
                continue
            if placed and node.args and (
                resolved in HOST_ARRAY_CASTS or resolved in HOST_CASTS
            ):
                names = {
                    n.id
                    for n in ast.walk(node.args[0])
                    if isinstance(n, ast.Name)
                }
                hit = sorted(names & placed)
                if hit:
                    yield self.finding(
                        module,
                        node,
                        f"host cast {resolved}() of device array "
                        f"'{hit[0]}' in hot-path '{qual}' synchronously "
                        "fetches the buffer back",
                    )
