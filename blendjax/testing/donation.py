"""Runtime donation audit: prove the hot path's device buffers are
REUSED in place, never silently copied.

Three donation contracts keep the live loop's device memory flat and
its dispatch path zero-copy, and all three are invisible to ordinary
tests until they regress as a 2x memory footprint or a per-step
realloc stall:

- the reservoir ring (``blendjax.data.echo.SampleReservoir``) is
  allocated once and every ``insert`` scatter updates it in place
  (donated buffer args) — its per-field device pointers never change;
- the fused echo draw (``blendjax.train.make_echo_fused_step``) reads
  the ring as a NON-donated argument — drawing must not move or copy
  the (potentially multi-GB) ring either;
- the donated train step writes the updated state back into the SAME
  buffers it consumed (``donate_argnums=(0,)`` + matching in/out
  layouts), so params/optimizer memory is one copy for the whole run.

:class:`DonationAudit` tracks ``unsafe_buffer_pointer()`` snapshots
per labeled pytree across the feeder -> reservoir insert -> fused
draw/step chain and asserts pointer stability.

Pointer reads are host-side metadata (no device sync); arrays whose
backend can't expose a pointer audit as ``None`` and are skipped
rather than failed, so the helper degrades gracefully off
CPU/TPU-local runtimes.
"""

from __future__ import annotations

import jax


def _leaf_pointer(leaf):
    """One leaf's buffer identity: the flat pointer for single-device
    arrays, a ``((device_id, pointer), ...)`` tuple per addressable
    shard for sharded ones (``unsafe_buffer_pointer`` itself raises on
    sharded arrays — without the per-shard read, a mesh-path audit
    would see nothing and report vacuous success). ``None`` when the
    runtime exposes neither."""
    get = getattr(leaf, "unsafe_buffer_pointer", None)
    if get is not None:
        try:
            return int(get())
        except Exception:
            pass
    shards = getattr(leaf, "addressable_shards", None)
    if shards is not None:
        try:
            return tuple(
                (s.device.id, int(s.data.unsafe_buffer_pointer()))
                for s in shards
            )
        except Exception:
            pass
    return None


def tree_pointers(tree) -> dict:
    """``{leaf path: buffer identity}`` for every array leaf of
    ``tree`` (:func:`_leaf_pointer`; ``None`` where the runtime can't
    expose one). Host metadata only — reading a pointer never syncs
    the device."""
    return {
        jax.tree_util.keystr(path): _leaf_pointer(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def pointers_stable(before: dict, after: dict) -> bool:
    """True when every leaf whose pointer is known on BOTH sides kept
    it — the in-place-update contract (donated scatter, donated step,
    non-donated fused read). Requires at least one leaf actually
    compared: a tree the runtime can't introspect at all (every
    pointer ``None``) is NOT evidence of reuse and reads unstable, the
    same rule as the empty-tree case."""
    keys = set(before) & set(after)
    compared = [
        k for k in keys
        if before[k] is not None and after[k] is not None
    ]
    if not compared:
        return False  # nothing auditable is not evidence of reuse
    return all(before[k] == after[k] for k in compared)


class DonationAudit:
    """Labeled pointer snapshots across a run.

    >>> audit = DonationAudit()
    >>> audit.snapshot("ring", reservoir._buffers)
    >>> ...  # inserts, fused draws/steps
    >>> audit.snapshot("ring", reservoir._buffers)
    >>> audit.stable("ring")
    True

    ``report()`` summarizes every label (snapshot count, distinct
    pointer sets, stability verdict). ``assert_stable()``
    raises with the offending leaves named, for test use."""

    def __init__(self) -> None:
        self._snaps: dict[str, list[dict]] = {}

    def snapshot(self, label: str, tree) -> dict:
        ptrs = tree_pointers(tree)
        self._snaps.setdefault(label, []).append(ptrs)
        return ptrs

    def stable(self, label: str) -> bool:
        snaps = self._snaps.get(label, [])
        if len(snaps) < 2:
            return False  # one snapshot proves nothing
        return all(
            pointers_stable(snaps[0], later) for later in snaps[1:]
        )

    def assert_stable(self, label: str) -> None:
        snaps = self._snaps.get(label, [])
        if len(snaps) < 2:
            raise AssertionError(
                f"donation audit {label!r}: need >= 2 snapshots, "
                f"have {len(snaps)}"
            )
        first = snaps[0]
        for i, later in enumerate(snaps[1:], start=1):
            compared = [
                k for k in set(first) & set(later)
                if first[k] is not None and later[k] is not None
            ]
            if not compared:
                # same rule as pointers_stable: an un-introspectable
                # tree must FAIL the audit, not pass it vacuously
                raise AssertionError(
                    f"donation audit {label!r}: no leaf exposed a "
                    f"buffer pointer between snapshot 0 and {i} — "
                    "reuse is unverifiable on this runtime, which is "
                    "not evidence of reuse"
                )
            moved = sorted(
                k for k in compared if first[k] != later[k]
            )
            if moved:
                raise AssertionError(
                    f"donation audit {label!r}: buffers moved between "
                    f"snapshot 0 and {i} (copied, not reused): {moved}"
                )

    def report(self) -> dict:
        out: dict = {}
        for label, snaps in self._snaps.items():
            distinct = len({
                tuple(sorted(s.items())) for s in snaps
            })
            out[label] = {
                "snapshots": len(snaps),
                "distinct_pointer_sets": distinct,
                "stable": self.stable(label),
            }
        return out


__all__ = ["DonationAudit", "pointers_stable", "tree_pointers"]
