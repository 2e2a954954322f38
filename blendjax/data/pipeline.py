"""Device feeding: host batches -> sharded global arrays, double-buffered.

This is the layer with no reference counterpart (the reference hands numpy
to torch and calls ``.cuda()`` implicitly in user code): host batches are
placed onto the mesh with ``jax.device_put`` under a ``NamedSharding``
along the ``data`` axis, and a prefetch ring keeps ``prefetch`` batches in
flight so host->HBM transfer overlaps the previous step's compute
(SURVEY.md §7 build step 3; BASELINE.json north star).

Multi-host: each process feeds its local shard;
``jax.make_array_from_process_local_data`` assembles the global array so a
v4-32-style mesh sees one logical batch (SURVEY.md §2.4 implication (b)).
"""

from __future__ import annotations

import collections
import hashlib
import threading

import numpy as np

from blendjax.obs.trace import TRACES_KEY, stamp_batch as trace_stamp_batch
from blendjax.scenario.accounting import SCENARIO_KEY
from blendjax.utils.logging import get_logger
from blendjax.utils.metrics import metrics

logger = get_logger("data")


def _require_jax():
    import jax  # deferred: producer processes never import jax

    return jax


def _representative_sharding(sharding):
    """ONE unwrap rule for "the pipeline's sharding, which may be a
    per-field dict": the first non-None entry (every entry shares one
    mesh — per-field specs differ, the mesh doesn't), or the value
    itself. Callers needing the mesh, the replicated layout, or the
    batch-axis shard count all resolve through here so they can never
    pick different representatives."""
    if isinstance(sharding, dict):
        return next(
            (s for s in sharding.values() if s is not None), None
        )
    return sharding


class DeviceFeeder:
    """Transfers host batch dicts to device with a prefetch ring.

    ``sharding`` may be:
    - None: default device placement (single chip).
    - a ``jax.sharding.Sharding``: applied to every tensor field.
    - a dict ``key -> Sharding`` for per-field layouts.

    ``_meta`` (per-item provenance like ``btid``) stays on host.

    ``throttle`` bounds how many transfers may be outstanding: each
    window entry is one representative array of a placed batch, and
    completed transfers are retired by a non-blocking per-entry
    readiness poll — the feeder blocks (once, on the oldest entry)
    only when the window is GENUINELY full of
    unfinished transfers. A consumer running ahead of the feeder
    therefore never costs a block (the old regime blocked on the oldest
    entry whenever the window filled, even with every transfer long
    done). Batches are yielded without waiting, so device-side data
    dependencies order the work; the window only stops the transfer
    queue from growing without bound (default depth 8).
    ``throttle=0``/None disables the bound.

    **Mesh mode**: pass ``mesh=`` (a named ``jax.sharding.Mesh``)
    instead of spelling the layout by hand — the batch sharding is
    derived over ``data_axis`` (``fsdp`` folded in, the layout
    ``blendjax.parallel.batch_sharding`` defines) and ``multihost``
    defaults to whether more than one jax process participates, so the
    SAME constructor drives one chip, an 8-chip pod slice, and a
    multi-host fleet. Placement is one call per batch, never a
    per-device host loop: single-process batches go up in ONE grouped
    ``device_put`` of every same-layout field (XLA slices shards
    device-side), multihost batches in one
    ``make_array_from_process_local_data`` per field (each process
    contributes its local rows to the global array).
    """

    #: bounded memo of placement plans keyed by batch-shape fingerprint
    PLAN_CACHE_LIMIT = 64

    def __init__(self, sharding=None, prefetch: int = 2,
                 multihost: bool | None = None,
                 throttle: int = 8, mesh=None, data_axis: str = "data"):
        if mesh is not None and sharding is None:
            from blendjax.parallel.sharding import batch_sharding

            sharding = batch_sharding(mesh, axis=data_axis)
        elif sharding is not None:
            from blendjax.parallel.sharding import validate_batch_sharding

            # an explicit feeder layout must still be a BATCH layout:
            # fsdp/tp partition parameters, and a wrong rule here would
            # otherwise fail deep inside the first placed jit dispatch
            for key, s in (
                sharding.items() if isinstance(sharding, dict)
                else [(None, sharding)]
            ):
                validate_batch_sharding(
                    s, data_axis=data_axis,
                    what=f"feeder field {key!r}" if key else "feeder batch",
                )
        if multihost is None:
            # auto only in mesh mode: a mesh spanning several processes
            # must assemble globals; explicit sharding keeps the old
            # single-host default.
            multihost = (
                mesh is not None and _require_jax().process_count() > 1
            )
        self.mesh = mesh
        self.data_axis = data_axis
        self.sharding = self._simplify(sharding)
        self.prefetch = max(1, int(prefetch))
        self.multihost = multihost
        self.throttle = int(throttle) if throttle else 0
        # Placement plans memoized per schema fingerprint: the same
        # stream yields the same field names/ranks every batch, so the
        # per-field sharding resolution + grouping runs once and
        # steady-state placement does zero per-batch re-derivation.
        self._place_plans: dict = {}

    @staticmethod
    def _simplify(sharding):
        """A sharding over exactly one device is semantically default
        placement — strip it, so a one-device run takes the plain
        ``device_put`` path. Multi-device shardings pass through
        untouched."""

        def one_device(s):
            try:
                if s is None or len(s.device_set) != 1:
                    return False
                # Only the DEFAULT device: stripping a sharding pinned to
                # another chip would silently relocate the data.
                jax = _require_jax()
                return next(iter(s.device_set)) == jax.devices()[0]
            except Exception:
                return False

        if isinstance(sharding, dict):
            return {k: (None if one_device(s) else s)
                    for k, s in sharding.items()}
        return None if one_device(sharding) else sharding

    def _field_tag(self, jax, k, v):
        """Placement-relevant signature of one batch entry — everything
        :meth:`_build_place_plan` branches on, and nothing else, so a
        memoized plan is exactly as correct as re-deriving it."""
        # SCENARIO_KEY: the batch-level domain-randomization stamp
        # (blendjax.scenario) — per-item provenance like _meta, and a
        # plain dict device_put would reject anyway.
        #
        # Host-side sidecars: per-item provenance and scalars — plain
        # ints AND rank-0 numpy values (the wire codec preserves either
        # form of a producer's ``btid`` stamp) — stay off-device:
        # multihost assembly would otherwise build a "replicated"
        # global from values that DIFFER per process (each producer
        # stamps its own id). Lists and other array-likes keep their
        # device placement.
        if k in ("_meta", TRACES_KEY, SCENARIO_KEY) or isinstance(
            v, (int, float)
        ) or getattr(v, "ndim", -1) == 0:
            return "pass"
        if isinstance(v, (tuple, dict, str)) or v is None:
            # Fused decode-plan sidecars (`_spec`/`_names`/`_geoms`/
            # `_pal`/`_rle` tuples, the `_refs` dict of already-placed
            # reference arrays): host metadata the fused step consumes
            # directly. Only reachable in driver-placement mode — the
            # feeder stage never sees post-plan batches.
            return "pass"
        if isinstance(v, jax.Array) and len(v.sharding.device_set) > 1:
            # Already an assembled multi-device global array (the
            # multihost chunk flush builds these) — re-placing would
            # force a reshard or a bogus re-assembly. Single-device
            # jax arrays deliberately fall through: a user-fed device
            # array still gets the configured batch sharding (or the
            # multihost global assembly), same as before.
            return "pass"
        if k in ("__packed__", "_packed"):
            # `__packed__` is the feeder-path reserved key; `_packed` is
            # the SAME buffer after device_stage attached its fused
            # decode plan (driver-placement mode places post-plan
            # batches). Both must replicate, never take the batch
            # sharding — byte-sharding a packed buffer would split
            # fields mid-array.
            return "packed"
        return getattr(v, "ndim", 0)

    def _build_place_plan(self, fingerprint) -> dict:
        """Resolve per-field placement actions ONCE per batch shape:
        the sharding lookups, rank-vs-spec checks, and same-layout
        grouping that used to run per batch now run per distinct
        fingerprint (one per stream schema in steady state)."""
        from jax.sharding import NamedSharding, PartitionSpec

        passthrough: list = []
        packed: list = []
        mh: list = []
        groups: dict = {}
        for k, tag in fingerprint:
            if tag == "pass":
                passthrough.append(k)
                continue
            if tag == "packed":
                packed.append(k)
                continue
            ndim = tag
            s = (
                self.sharding.get(k)
                if isinstance(self.sharding, dict)
                else self.sharding
            )
            spec_rank = len(getattr(s, "spec", ()) or ())
            if s is not None and ndim < spec_rank:
                # Fields of lower rank than the configured spec can't
                # take the batch sharding: replicate instead. (True
                # scalars never reach here — they stay on host via the
                # "pass" tag; this covers e.g. a rank-1 field under a
                # rank-2 per-field spec.)
                s = NamedSharding(s.mesh, PartitionSpec())
            if self.multihost and s is not None:
                mh.append((k, s))
            else:
                groups.setdefault(s, []).append(k)
        # __packed__: a whole batch flattened to one uint8 buffer
        # (TileStreamDecoder). It must never take the batch sharding —
        # byte-sharding a buffer whose fields aren't device-aligned
        # would split fields mid-array; the unpacked fields are
        # resharded after the decode jit instead. On a multi-device
        # mesh the buffer replicates (ONE placement call) so the
        # decode/fused-step jit sees a single device set; packed
        # buffers only exist single-host.
        packed_sharding = None
        if packed:
            mesh = getattr(
                _representative_sharding(self.sharding), "mesh", None
            )
            if mesh is not None:
                packed_sharding = NamedSharding(mesh, PartitionSpec())
        return {
            "pass": tuple(passthrough),
            "packed": tuple(packed),
            "packed_sharding": packed_sharding,
            "mh": tuple(mh),
            "groups": tuple(
                (s, tuple(keys)) for s, keys in groups.items()
            ),
        }

    def _place(self, batch: dict) -> dict:
        jax = _require_jax()
        # Same-layout tensor fields are grouped and placed with ONE
        # device_put call on the whole sub-dict (the runtime fans the
        # group out itself): a batch is one placement, not one RPC per
        # field — and never a per-device host loop (bjx-lint BJX111
        # guards that property on mesh hot paths).
        fingerprint = tuple(
            (k, self._field_tag(jax, k, v)) for k, v in batch.items()
        )
        plan = self._place_plans.get(fingerprint)
        if plan is None:
            if len(self._place_plans) >= self.PLAN_CACHE_LIMIT:
                self._place_plans.clear()
            plan = self._place_plans[fingerprint] = (
                self._build_place_plan(fingerprint)
            )
        out = {k: batch[k] for k in plan["pass"]}
        ps = plan["packed_sharding"]
        for k in plan["packed"]:
            out[k] = (
                jax.device_put(batch[k]) if ps is None
                else jax.device_put(batch[k], ps)
            )
        for k, s in plan["mh"]:
            out[k] = jax.make_array_from_process_local_data(s, batch[k])
        for s, keys in plan["groups"]:
            fields = {k: batch[k] for k in keys}
            placed = (
                jax.device_put(fields) if s is None
                else jax.device_put(fields, s)
            )
            out.update(placed)
        return out

    def place(self, batch: dict) -> dict:
        """One grouped, span-accounted, trace-stamped placement of a
        host batch — the entry :class:`blendjax.train.TrainDriver`
        calls when placement is folded into the dispatch
        (``TrainDriver(place=feeder.place)``): the async transfer is
        committed at submit time and overlaps the in-flight steps the
        driver ring tracks, instead of running as a separate
        host-blocking feeder stage."""
        with metrics.span("feed.place"):
            db = self._place(batch)
        # Frame trace: the host->device transfer was dispatched for
        # every field of this batch (fast no-op when untraced).
        trace_stamp_batch(db, "place")
        return db

    @staticmethod
    def _largest(batch):
        arrays = [
            v for k, v in batch.items()
            if k != "_meta" and hasattr(v, "is_ready")
        ]
        return max(arrays, key=lambda v: v.size, default=None)

    @staticmethod
    def _is_done(arr) -> bool:
        """Non-blocking readiness poll for one window entry (shared
        definition: :func:`blendjax.utils.device.transfer_done`)."""
        from blendjax.utils.device import transfer_done

        return transfer_done(arr)

    def __call__(self, host_batches):
        """Iterate device batches, keeping ``prefetch`` transfers in flight
        ahead of the consumer (flax-style prefetch ring) and at most
        ``throttle`` transfers outstanding on the device.

        Completion is tracked per entry: a cheap ``is_ready`` poll
        retires finished transfers from anywhere in the window, so the
        feeder only pays a blocking wait (on the oldest entry's
        representative array — the batch's largest) when the ring is
        genuinely full of unfinished work; the array it waits on was
        placed ``throttle`` batches ago so the wait is usually
        trivial."""
        jax = _require_jax()
        ring = collections.deque()
        window: collections.deque = collections.deque()
        it = iter(host_batches)

        def place(hb):
            if self.throttle:
                still = [
                    w for w in window
                    if w is not None and not self._is_done(w)
                ]
                window.clear()
                window.extend(still)
                while len(window) >= self.throttle:
                    oldest = window.popleft()
                    metrics.count("feed.throttle_blocks")
                    with metrics.span("feed.throttle_wait"):
                        jax.block_until_ready(oldest)
            db = self.place(hb)
            if self.throttle:
                window.append(self._largest(db))
            return db

        try:
            while True:
                while len(ring) < self.prefetch:
                    try:
                        ring.append(place(next(it)))
                    except StopIteration:
                        while ring:
                            yield ring.popleft()
                        return
                yield ring.popleft()
        finally:
            ring.clear()
            window.clear()


class TileStreamDecoder:
    """Pipeline stage pair for tile-delta-encoded image streams
    (``blendjax.ops.tiles`` wire convention).

    ``host_stage`` runs before the :class:`DeviceFeeder`: it strips each
    producer's one-time ``<name>__tileref`` reference image (placing its
    tiled view on device, replicated), remembers the decode geometry, and
    queues per-batch decode plans. ``device_stage`` runs after the feeder:
    batches whose (small) ``__tileidx``/``__tiles`` arrays were transferred
    are reconstructed into exact full ``<name>`` images by a jitted batched
    scatter — so only changed tiles ever cross host->device.

    Refs are keyed per (field, producer btid): ZMQ PUSH is FIFO per
    producer, so a producer's ref always precedes its deltas even under
    fair fan-in interleaving.

    ``chunk=K`` coalesces K consecutive compatible tile batches into ONE
    transfer and ONE decode call yielding a superbatch with a leading
    chunk axis — (K, B, H, W, C) — for consumption by
    :func:`blendjax.train.make_chunked_supervised_step`. One device
    round trip then covers K batches, which is what keeps throughput up
    on high-latency device links. Batches group only while their packed
    layout and reference images match — pin
    ``TileBatchPublisher(capacity=...)`` across a producer fleet so
    groups never fragment; mismatches flush a shorter group (one extra
    decode compilation per distinct K'). Chunked fields reshard to the
    configured batch sharding with the chunk axis replicated.
    """

    def __init__(self, sharding=None, multihost: bool = False,
                 chunk: int = 1, chunk_strict: bool = False,
                 emit_packed: bool = False):
        self.sharding = sharding
        self.multihost = multihost
        self.chunk = max(1, int(chunk))
        # emit_packed=True skips the decode jit: device_stage yields
        # ``{"_packed", "_refs", "_spec", "_names", "_geoms", ...}`` for
        # tile groups and ``{"_packed", "_spec", "_pal", ...}`` for
        # full-frame palette groups, consumed by
        # :func:`blendjax.train.make_fused_tile_step`, which fuses the
        # decode into the train jit — one device call per chunk group
        # instead of two, and zero standalone decode.dispatch spans.
        # Both group kinds always route through the chunk path (K'=1
        # groups when chunk==1).
        self.emit_packed = bool(emit_packed)
        # strict=True restores the fail-fast contract: any non-tile
        # message in a chunk>1 stream raises instead of degrading to a
        # K'=1 superbatch (see host_stage).
        self.chunk_strict = bool(chunk_strict)
        self._warned_mixed = False
        self._refs: dict = {}       # (name, btid) -> device ref_tiles
        self._host_refs: dict = {}  # (name, btid) -> host copy (dedup)
        self._ref_digest: dict = {}  # (name, btid) -> stable content hash
        self._shapes: dict = {}  # name -> (h, w, c, tile)
        self._skipped: set = set()  # warned-once missing-ref keys
        self._mh_checked: dict = {}  # field -> fleet-verified digest
        self._plans: collections.deque = collections.deque()
        self._decode = None
        self._decode_chunk = None
        self._decode_mh = None
        self._decode_mh_chunk = None
        self._decode_pal = None
        self._decode_pal_chunk = None

    def reset(self) -> None:
        """Drop queued per-batch decode plans (call when re-iterating a
        pipeline: batches a feeder prefetched but never yielded leave
        stale plans behind). Refs survive — producers send them once."""
        self._plans.clear()

    def _replicated(self):
        jax = _require_jax()
        s = _representative_sharding(self.sharding)
        if s is not None and hasattr(s, "mesh"):
            from jax.sharding import NamedSharding, PartitionSpec

            return NamedSharding(s.mesh, PartitionSpec())
        return None

    def _field_sharding(self, key):
        """Configured batch sharding for one field (dict- or single-)."""
        return (
            self.sharding.get(key)
            if isinstance(self.sharding, dict)
            else self.sharding
        )

    def _pin_superbatch(self, fields: dict) -> None:
        """Move decoded (K, B, ...) superbatch fields to the configured
        batch sharding with the chunk axis replicated, in place (async
        reshard; no-op on one device). ONE copy of this logic — the
        chunk and mhchunk branches must never diverge on output
        layout."""
        jax = _require_jax()
        for k, v in fields.items():
            s = self._field_sharding(k)
            spec = getattr(s, "spec", None)
            if (
                s is not None
                and spec is not None
                and getattr(v, "ndim", 0) >= len(spec) + 1
            ):
                from jax.sharding import NamedSharding, PartitionSpec

                fields[k] = jax.device_put(
                    v, NamedSharding(s.mesh, PartitionSpec(None, *spec))
                )

    def _decode_mesh(self):
        """(mesh, data_axis) for the sharded Pallas decode — taken from
        the configured batch sharding's mesh and its leading spec axis;
        (None, 'data') on single-device/unsharded pipelines (the decode
        then auto-selects as before)."""
        s = _representative_sharding(self.sharding)
        mesh = getattr(s, "mesh", None)
        if mesh is None or np.prod(list(mesh.shape.values())) <= 1:
            return None, "data"
        spec = getattr(s, "spec", None) or ()
        axis = spec[0] if spec and isinstance(spec[0], str) else "data"
        return mesh, axis

    def host_stage(self, host_batches):
        from blendjax.ops import tiles as T

        jax = _require_jax()
        group: dict = {}
        mh_group: dict = {}  # multihost chunk>1 buffering (lockstep flush)
        pal_group: dict = {}  # chunk>1 full-frame palette grouping
        for hb in host_batches:
            btid = hb.get("btid")
            new_refs: dict = {}
            T.pop_stream_refs(hb, new_refs, btid)
            for ref in new_refs.values():
                # keyframe refs are wire bytes too (ratio honesty)
                metrics.count("tiles.wire_bytes", int(ref.nbytes))
            for key, ref in new_refs.items():
                # Keyframe refs usually repeat the one we already hold:
                # skip the device placement then (host compare is cheap
                # next to a multi-MB transfer).
                cached = self._host_refs.get(key)
                if cached is not None and np.array_equal(cached, ref):
                    continue
                self._host_refs[key] = np.asarray(ref).copy()
                # Stable digest (NOT Python hash(): per-process salted),
                # so chunk-group keys and the multihost fleet check
                # compare identically across processes.
                self._ref_digest[key] = int.from_bytes(
                    hashlib.blake2b(
                        self._host_refs[key].tobytes(), digest_size=8
                    ).digest(), "little",
                )
                tile = T.geom_tile(tuple(
                    int(v) for v in hb.get(
                        key[0] + T.TILESHAPE_SUFFIX, [0, 0, 0, T.TILE]
                    )
                ))
                s = self._replicated()
                if self.multihost and s is not None:
                    # Global replicated ref: every process holds the same
                    # tiled view on its local devices (multihost tile
                    # streams require fleet-shared reference content —
                    # see _host_stage_multihost).
                    ref_tiles = jax.make_array_from_process_local_data(
                        s, T.tile_ref_np(np.asarray(ref), tile)
                    )
                else:
                    ref_tiles = T.tile_ref(ref, tile)
                    if s is not None:
                        ref_tiles = jax.device_put(ref_tiles, s)
                self._refs[key] = ref_tiles
            # Deferred run-length wire frames ("ndr", docs/wire-protocol
            # .md): the packed buffers + plans ride the batch; validate
            # HERE (host side, the ndz bounds/truncation guards carried
            # over) and expand inside the decode/train jit below.
            rle_groups = T.pop_rle_batches(hb)
            if rle_groups:
                if self.multihost:
                    # Correctness-first fallback, like the pal path:
                    # expand on host so the fields ride the multihost
                    # global-array assembly.
                    for base, (shape, isz, cap) in rle_groups:
                        hb[base] = T.rle_expand_packed_np(
                            hb.pop(base + T.NDR_SUFFIX), shape, isz, cap
                        )
                    rle_groups = ()
                else:
                    decoded = 0
                    packed_bytes = 0
                    for base, (shape, isz, cap) in rle_groups:
                        buf = hb[base + T.NDR_SUFFIX]
                        T.rle_validate_packed(buf, shape, isz, cap)
                        packed_bytes += int(buf.nbytes)
                        n = 1
                        for s in shape:
                            n *= int(s)
                        decoded += n
                    metrics.count("rle.batches")
                    metrics.count("rle.packed_bytes", packed_bytes)
                    metrics.count("rle.decoded_bytes", decoded)
            has_tiles = any(
                k.endswith(T.TILESHAPE_SUFFIX) for k in hb
            )
            pal_groups = T.pop_frame_palette_batches(hb)
            if pal_groups or (rle_groups and not has_tiles):
                if self.multihost:
                    # Correctness-first fallback: expand on host and let
                    # the batch ride the existing raw paths (multihost
                    # global assembly). The device-gather paths below
                    # are the single-host configurations the non-sparse
                    # codec targets.
                    for name, (h_, w_, c_, bits) in pal_groups:
                        hb[name] = T.pop_frame_palette_payload(
                            hb, name, bits, h_, w_, c_,
                            T.expand_palette_frames_np,
                        )
                else:
                    arrays = {
                        k: v for k, v in hb.items()
                        if isinstance(v, np.ndarray)
                    }
                    rest = {k: v for k, v in hb.items() if k not in arrays}
                    with metrics.span("tiles.pack"):
                        buf, spec = T.pack_fields(arrays)
                    if pal_groups:
                        metrics.count("pal.batches")
                        metrics.count("pal.wire_bytes", int(buf.nbytes))
                    for name, (h_, w_, c_, bits) in pal_groups:
                        lead = int(
                            arrays[
                                name + T.FRAMEPAL_SUFFIXES[bits]
                            ].shape[0]
                        )
                        metrics.count(
                            "pal.decoded_bytes", int(h_ * w_ * c_) * lead
                        )
                    if self.chunk == 1 and not self.emit_packed:
                        self._plans.append(
                            ("pal", spec, rest, tuple(pal_groups),
                             rle_groups)
                        )
                        yield {"__packed__": buf}
                        continue
                    # chunk>1: coalesce K packed pal batches into ONE
                    # stacked transfer + one scanned step, exactly like
                    # the tile chunk path (K transfers + K step
                    # dispatches collapse K-fold). emit_packed
                    # routes through this grouped form too (K'=1 groups
                    # when chunk==1): the fused step consumes the
                    # stacked (K', total) layout.
                    gkey = (spec, tuple(pal_groups), rle_groups)
                    if pal_group and pal_group["key"] != gkey:
                        yield from self._flush_pal_group(pal_group)
                    if not pal_group:
                        pal_group.update(key=gkey, bufs=[], rests=[])
                    pal_group["bufs"].append(buf)
                    pal_group["rests"].append(rest)
                    if len(pal_group["bufs"]) == self.chunk:
                        yield from self._flush_pal_group(pal_group)
                    continue
            groups = T.pop_tile_batches(hb)
            names = []
            missing = False
            for name, geom in groups:
                if (name, btid) not in self._refs:
                    # Fair fan-in delivered this producer's (keyframe)
                    # reference to another consumer: skip until one
                    # arrives here (bounded spam via once-per-key log).
                    if (name, btid) not in self._skipped:
                        self._skipped.add((name, btid))
                        logger.warning(
                            "skipping tile batches for %r from producer "
                            "%r until its reference image arrives (use "
                            "TileBatchPublisher(ref_interval=N) for "
                            "multi-consumer streams)", name, btid,
                        )
                    missing = True
                    continue
                self._shapes[name] = geom
                names.append(name)
            if missing:
                continue  # drop the whole batch, keep plans aligned
            if names and self.multihost:
                if self.chunk > 1:
                    yield from self._mh_group_add(mh_group, hb, names, btid)
                else:
                    yield from self._host_stage_multihost(hb, names, btid)
                continue
            if not names:
                if self.chunk > 1 or self.emit_packed:
                    if self.chunk_strict:
                        raise RuntimeError(
                            "chunk>1 requires an all-tile-encoded stream: "
                            "a non-tile message arrived, and the chunked "
                            "step consumer expects (K, B, ...) "
                            "superbatches only (chunk_strict=True)"
                        )
                    # Degrade instead of killing training: flush the
                    # in-flight group, then ship this raw batch as a
                    # K'=1 superbatch (device_stage adds the leading
                    # chunk axis post-placement so batch sharding stays
                    # on the batch dim). One misconfigured producer in a
                    # fleet costs throughput, not the run.
                    if not self._warned_mixed:
                        self._warned_mixed = True
                        logger.warning(
                            "non-tile message in a chunk=%d stream: "
                            "flushing the group and degrading to K'=1 "
                            "superbatches for raw batches (pass "
                            "chunk_strict=True to fail fast instead)",
                            self.chunk,
                        )
                    yield from self._flush_group(group)
                    yield from self._flush_mh_group(mh_group)
                    yield from self._flush_pal_group(pal_group)
                    # Surfaced in the bench/metrics report: a fleet whose
                    # chunk groups silently degrade to K'=1 loses ~10x
                    # throughput, and one log line is easy to miss.
                    metrics.count("tiles.degraded_groups")
                    self._plans.append(("raw1",))
                    yield hb
                    continue
                self._plans.append(None)
                yield hb
                continue
            # Collapse every ndarray field of a tile batch into ONE uint8
            # buffer: the whole batch then crosses host->device as a
            # single transfer (instead of one per field) and is
            # re-sliced on device under the decode jit.
            arrays = {
                k: v for k, v in hb.items() if isinstance(v, np.ndarray)
            }
            rest = {k: v for k, v in hb.items() if k not in arrays}
            with metrics.span("tiles.pack"):
                buf, spec = T.pack_fields(arrays)
            metrics.count("tiles.batches")
            metrics.count("tiles.wire_bytes", int(buf.nbytes))
            for name in names:
                h_, w_, c_ = self._shapes[name][:3]
                lead = int(arrays[name + T.TILEIDX_SUFFIX].shape[0])
                # what the equivalent raw frames would have transferred
                metrics.count(
                    "tiles.decoded_bytes", int(h_ * w_ * c_) * lead
                )
            if self.chunk == 1 and not self.emit_packed:
                # Pin the device refs + geometry INTO the plan: host_stage
                # runs `prefetch` batches ahead of device_stage, and a
                # producer restarting with new scene content would replace
                # self._refs[(name, btid)] while this batch is in flight —
                # a decode-time lookup would then reconstruct against the
                # wrong reference.
                self._plans.append((
                    names, spec, rest,
                    {n: self._refs[(n, btid)] for n in names},
                    tuple(self._shapes[n] for n in names),
                    rle_groups,
                ))
                yield {"__packed__": buf}
                continue
            # Chunk mode: group while the packed layout AND reference
            # content match (one shared ref lets the whole group decode
            # flattened in a single call).
            gkey = (
                tuple(names), spec,
                tuple(self._ref_digest.get((n, btid)) for n in names),
                rle_groups,
            )
            if group and group["key"] != gkey:
                yield from self._flush_group(group)
            if not group:
                # Refs/geoms pinned at group-formation time (same
                # staleness hazard as the chunk==1 plan); the gkey digest
                # guarantees later members share this ref content.
                group.update(
                    key=gkey, bufs=[], rests=[],
                    refs={n: self._refs[(n, btid)] for n in names},
                    geoms=tuple(self._shapes[n] for n in names),
                    rle=rle_groups,
                )
            group["bufs"].append(buf)
            group["rests"].append(rest)
            if len(group["bufs"]) == self.chunk:
                yield from self._flush_group(group)
        yield from self._flush_group(group)
        yield from self._flush_mh_group(mh_group)
        yield from self._flush_pal_group(pal_group)

    def _flush_pal_group(self, pal_group):
        """Emit a buffered palette chunk group (possibly shorter than
        ``chunk``) as one stacked packed transfer; no-op when empty."""
        if not pal_group:
            return
        spec, pal_groups, rle_groups = pal_group["key"]
        self._plans.append(
            ("palchunk", spec, pal_group["rests"], pal_groups, rle_groups)
        )
        stacked = np.stack(pal_group["bufs"])
        pal_group.clear()
        yield {"__packed__": stacked}

    def _mh_fields(self, hb, names, btid):
        """Shared multihost prep: split ndarray fields from sidecars,
        resolve the fleet-shared reference per field (with divergence
        enforcement), and broadcast per-stream palettes per row.

        SPMD contract: every process must stream identical wire shapes
        (pin ``TileBatchPublisher(capacity=...)`` across the fleet) and
        fleet-shared reference content — the global batch decodes
        against ONE replicated reference per field. Divergence is an
        ERROR, not a warning: rows decoded against the wrong reference
        are silent training-data corruption. Enforcement is two-level:

        - cross-process: on the FIRST ref selection for a field, the
          chosen digest is all-gathered over ``jax.distributed`` and any
          mismatch raises on every process (catches per-host scene-
          version skew at startup). Checked once per field. Liveness
          caveat (inherent to SPMD collectives): if one process dies
          BEFORE reaching a field's gather (e.g. a local divergence
          raise on another field), peers block in the collective until
          the distributed runtime's failure detection kicks in — the
          run still fails, but via the coordinator timeout rather than
          this error message.
        - within-process: any producer whose ref digest differs from the
          fleet-shared one raises immediately (replaces the old
          warn-and-corrupt path; ADVICE r2 medium).
        """
        from blendjax.ops import tiles as T

        fields = {}
        rest = {}
        for k, v in hb.items():
            if isinstance(v, np.ndarray) and v.ndim >= 1:
                fields[k] = v
            else:
                rest[k] = v
        refs = {}
        for name in names:
            # Deterministic shared ref: the first producer's (insertion
            # order), so every process resolves the same content when
            # the fleet shares one scene background.
            first_key = next(k for k in self._refs if k[0] == name)
            shared = self._ref_digest.get(first_key)
            mine = self._ref_digest.get((name, btid))
            if mine != shared:
                raise RuntimeError(
                    f"multihost tile stream {name!r}: producer {btid!r} "
                    "sent a reference image differing from the fleet-"
                    "shared one — its rows would silently decode against "
                    "the wrong reference. Pin one scene background "
                    "across the fleet (same seed/scene), or run "
                    "single-host pipelines per producer group."
                )
            self._assert_fleet_digest(name, shared)
            refs[name] = self._refs[first_key]
            pal_key = name + T.PALETTE_SUFFIX
            if pal_key in fields:
                # Per-row palettes: expand_palette_tiles' grouped path
                # gathers row i through palette row i, and the global
                # assembly stacks processes on the leading axis, so each
                # process's rows keep their own palette.
                packed_key = next(
                    name + s
                    for s in T.TILEPAL_SUFFIXES.values()
                    if name + s in fields
                )
                b = fields[packed_key].shape[0]
                pal = fields[pal_key]
                if pal.ndim == 2:  # batch-level palette: one row each
                    fields[pal_key] = np.ascontiguousarray(
                        np.broadcast_to(pal[None], (b, *pal.shape))
                    )
        return fields, rest, refs

    def _assert_fleet_digest(self, name, digest) -> None:
        """One-time cross-process agreement check on a field's selected
        reference digest (no-op single-process and on re-checks)."""
        if name in self._mh_checked:
            return
        jax = _require_jax()
        if jax.process_count() <= 1:
            self._mh_checked[name] = digest
            return
        from jax.experimental import multihost_utils

        # Two uint32 words, not one uint64: with jax_enable_x64 off (the
        # default) a uint64 array would be canonicalized to uint32 and
        # the gather would silently compare only the low half.
        words = np.asarray(
            [digest & 0xFFFFFFFF, digest >> 32], dtype=np.uint32
        )
        everyone = np.asarray(
            multihost_utils.process_allgather(words)
        ).reshape(-1, 2)
        if not (everyone == everyone[0]).all():
            digests = {
                int(lo) | (int(hi) << 32) for lo, hi in everyone.tolist()
            }
            raise RuntimeError(
                f"multihost tile stream {name!r}: processes selected "
                f"DIFFERENT fleet references (digests {digests}) "
                "— the assembled global batch would decode some rows "
                "against the wrong content. Pin one scene background "
                "across all hosts."
            )
        # Record only after the fleet agrees: a caller that catches the
        # divergence error and keeps iterating stays checked (and keeps
        # failing) instead of silently passing from then on.
        self._mh_checked[name] = digest

    def _host_stage_multihost(self, hb, names, btid):
        """Tile batch -> per-field global assembly plan (multihost,
        per-batch decode).

        The packed single-buffer transfer cannot shard (bytes, not
        batch), so each batch-leading tile field rides the feeder's
        ``make_array_from_process_local_data`` path individually and the
        DECODE runs on the assembled global batch — GSPMD partitions the
        scatter shard-locally per device (or the shard_map Pallas kernel
        takes over when eligible), which is exactly "decode
        shard-locally, assemble globally".
        """
        fields, rest, refs = self._mh_fields(hb, names, btid)
        self._plans.append(
            ("mh", tuple(names), tuple(self._shapes[n] for n in names),
             rest, refs)
        )
        yield fields

    def _mh_group_add(self, mh_group, hb, names, btid):
        """Multihost chunk>1: buffer compatible tile batches and flush
        count-based — the SPMD contract (identical wire shapes + shared
        refs on every process, ``_mh_fields``) makes the flush boundary
        deterministic across processes, so each process contributes the
        same group shape to the global assembly (lockstep flush,
        VERDICT r2 item 4)."""
        fields, rest, refs = self._mh_fields(hb, names, btid)
        gkey = (
            tuple(names),
            tuple(sorted(
                (k, v.dtype.str, v.shape) for k, v in fields.items()
            )),
            tuple(self._ref_digest.get((n, btid)) for n in names),
        )
        if mh_group and mh_group["key"] != gkey:
            yield from self._flush_mh_group(mh_group)
        if not mh_group:
            mh_group.update(
                key=gkey, fields=[], rests=[], refs=refs,
                names=tuple(names),
                geoms=tuple(self._shapes[n] for n in names),
            )
        mh_group["fields"].append(fields)
        mh_group["rests"].append(rest)
        if len(mh_group["fields"]) == self.chunk:
            yield from self._flush_mh_group(mh_group)

    def _flush_mh_group(self, mh_group):
        """Assemble a buffered multihost chunk group into ONE global
        array per field — local (K', B_local, ...) stacks become global
        (K', B_global, ...) arrays sharded ``P(None, data)`` via
        ``make_array_from_process_local_data`` (one placement call per
        field for the whole group), decoded in one call downstream."""
        if not mh_group:
            return
        jax = _require_jax()
        from jax.sharding import NamedSharding, PartitionSpec

        stacked = {
            k: np.stack([f[k] for f in mh_group["fields"]])
            for k in mh_group["fields"][0]
        }
        out = {}
        for k, v in stacked.items():
            s = self._field_sharding(k)
            spec = getattr(s, "spec", None)
            if s is None or spec is None:
                # Unsharded multihost pipelines don't exist (the feeder
                # needs a mesh to assemble), but keep a sane fallback.
                out[k] = jax.device_put(v)
                continue
            if v.ndim >= len(spec) + 1:
                gs = NamedSharding(s.mesh, PartitionSpec(None, *spec))
            else:  # low-rank sidecar: replicate
                gs = NamedSharding(s.mesh, PartitionSpec())
            out[k] = jax.make_array_from_process_local_data(gs, v)
        self._plans.append((
            "mhchunk", mh_group["names"], mh_group["geoms"],
            mh_group["rests"], mh_group["refs"],
        ))
        mh_group.clear()
        yield out

    def _flush_group(self, group):
        """Emit a buffered chunk group (possibly shorter than ``chunk``)
        as one stacked packed transfer; no-op when empty."""
        if not group:
            return
        names, spec, _digests, rle_groups = group["key"]
        self._plans.append(
            ("chunk", names, spec, group["rests"],
             group["refs"], group["geoms"], rle_groups)
        )
        stacked = np.stack(group["bufs"])
        group.clear()
        yield {"__packed__": stacked}

    def device_stage(self, device_batches):
        from blendjax.ops import tiles as T

        jax = _require_jax()
        if self._decode is None:
            mesh, axis = self._decode_mesh()

            def _decode_packed(packed, refs, spec, names, geoms, rle=()):
                fields = T.expand_rle_fields(
                    T.unpack_fields(packed, spec), rle
                )
                for name, geom in zip(names, geoms):
                    idx = fields.pop(name + T.TILEIDX_SUFFIX)
                    tiles = T.pop_tile_payload(
                        fields, name, geom, T.expand_palette_tiles
                    )
                    fields[name] = T.decode_tile_delta(
                        refs[name], idx, tiles, geom[:3],
                        mesh=mesh, data_axis=axis,
                    )
                return fields

            self._decode = jax.jit(
                _decode_packed,
                static_argnames=("spec", "names", "geoms", "rle"),
            )
        if self._decode_chunk is None:
            import functools

            mesh, axis = self._decode_mesh()
            self._decode_chunk = jax.jit(
                functools.partial(
                    T.decode_packed_superbatch, mesh=mesh, data_axis=axis
                ),
                static_argnames=("spec", "names", "geoms", "rle_groups"),
            )
        if self._decode_mh is None:
            mesh, axis = self._decode_mesh()

            def _decode_fields(fields, refs, names, geoms):
                for name, geom in zip(names, geoms):
                    idx = fields.pop(name + T.TILEIDX_SUFFIX)
                    tiles = T.pop_tile_payload(
                        fields, name, geom, T.expand_palette_tiles
                    )
                    fields[name] = T.decode_tile_delta(
                        refs[name], idx, tiles, geom[:3],
                        mesh=mesh, data_axis=axis,
                    )
                return fields

            self._decode_mh = jax.jit(
                _decode_fields, static_argnames=("names", "geoms")
            )
        if self._decode_pal is None:
            # Shared fusable entry points (blendjax.ops.tiles): the SAME
            # decode program make_fused_tile_step traces into the train
            # jit, wrapped standalone here for decode-then-step
            # consumers — the two paths cannot drift.
            self._decode_pal = jax.jit(
                T.decode_packed_pal_batch,
                static_argnames=("spec", "pal_groups", "rle_groups"),
            )
            self._decode_pal_chunk = jax.jit(
                T.decode_packed_pal_superbatch,
                static_argnames=("spec", "pal_groups", "rle_groups"),
            )
        if self._decode_mh_chunk is None:
            mesh, axis = self._decode_mesh()

            def _decode_fields_chunk(fields, refs, names, geoms):
                # fields are assembled global (K, B, ...) arrays; each
                # name's payload decodes flattened over (K*B) in one
                # scatter call (mirrors decode_packed_superbatch).
                for name, geom in zip(names, geoms):
                    idx = fields.pop(name + T.TILEIDX_SUFFIX)
                    k, b = idx.shape[:2]

                    def flat(v):
                        return v.reshape((k * b,) + tuple(v.shape[2:]))

                    for suf in (
                        T.TILES_SUFFIX, *T.TILEPAL_SUFFIXES.values(),
                        T.PALETTE_SUFFIX,
                    ):
                        if name + suf in fields:
                            fields[name + suf] = flat(fields[name + suf])
                    tiles = T.pop_tile_payload(
                        fields, name, geom, T.expand_palette_tiles
                    )
                    img = T.decode_tile_delta(
                        refs[name], flat(idx), tiles, geom[:3],
                        mesh=mesh, data_axis=axis,
                    )
                    fields[name] = img.reshape(k, b, *img.shape[1:])
                return fields

            self._decode_mh_chunk = jax.jit(
                _decode_fields_chunk, static_argnames=("names", "geoms")
            )
        for db in device_batches:
            plan = self._plans.popleft()
            if plan is not None and plan[0] == "mh":
                _, names, geoms, rest, refs = plan
                meta = db.pop("_meta", None)
                with metrics.span("decode.dispatch"):
                    fields = self._decode_mh(
                        db, refs, names=names, geoms=geoms
                    )
                fields.update(rest)
                if meta is not None:
                    fields["_meta"] = meta
                trace_stamp_batch(fields, "decode")
                yield fields
                continue
            if plan is not None and plan[0] == "mhchunk":
                _, names, geoms, rests, refs = plan
                db.pop("_meta", None)
                with metrics.span("decode.dispatch"):
                    fields = self._decode_mh_chunk(
                        db, refs, names=names, geoms=geoms
                    )
                self._pin_superbatch(fields)
                fields["_meta"] = rests
                trace_stamp_batch(fields, "decode")
                yield fields
                continue
            if plan is not None and plan[0] == "pal":
                _, spec, rest, pal_groups, rle_groups = plan
                with metrics.span("decode.dispatch"):
                    fields = self._decode_pal(
                        db.pop("__packed__"), spec=spec,
                        pal_groups=pal_groups, rle_groups=rle_groups,
                    )
                # packed buffer travels unsharded: reshard decoded fields
                # to their configured layouts (no-op on one device)
                for k, v in fields.items():
                    s = self._field_sharding(k)
                    if s is not None and getattr(v, "ndim", 0) >= len(
                        getattr(s, "spec", ()) or ()
                    ):
                        fields[k] = jax.device_put(v, s)
                db.update(rest)
                db.update(fields)
                trace_stamp_batch(db, "decode")
                yield db
                continue
            if plan is not None and plan[0] == "palchunk":
                _, spec, rests, pal_groups, rle_groups = plan
                if self.emit_packed:
                    # Fused-step form: the still-encoded stacked buffer
                    # plus its decode plan — the palette expand (and any
                    # deferred run-length expansion) happens INSIDE the
                    # train jit (make_fused_tile_step), so no standalone
                    # decode.dispatch call exists on this path and
                    # decoded frames never round-trip as standalone
                    # jax.Arrays.
                    db["_packed"] = db.pop("__packed__")
                    db["_spec"] = spec
                    db["_pal"] = pal_groups
                    db["_rle"] = rle_groups
                    db["_meta"] = rests
                    yield db
                    continue
                with metrics.span("decode.dispatch"):
                    fields = self._decode_pal_chunk(
                        db.pop("__packed__"), spec=spec,
                        pal_groups=pal_groups, rle_groups=rle_groups,
                    )
                self._pin_superbatch(fields)
                db["_meta"] = rests
                db.update(fields)
                trace_stamp_batch(db, "decode")
                yield db
                continue
            if plan is not None and plan[0] == "raw1":
                # Mixed-stream degradation (chunk_strict=False): lift the
                # already-placed raw batch to a K'=1 superbatch. The
                # expand happens AFTER device placement so the batch dim
                # kept its data sharding; v[None] infers (None, *spec).
                for k, v in list(db.items()):
                    if k != "_meta" and getattr(v, "ndim", 0) >= 1:
                        db[k] = v[None]
                yield db
                continue
            if plan is not None and plan[0] == "chunk":
                _, names, spec, rests, refs, geoms, rle_groups = plan
                if self.emit_packed:
                    db["_packed"] = db.pop("__packed__")
                    db["_refs"] = refs
                    db["_spec"] = spec
                    db["_names"] = tuple(names)
                    db["_geoms"] = geoms
                    db["_rle"] = rle_groups
                    db["_meta"] = rests
                    yield db
                    continue
                with metrics.span("decode.dispatch"):
                    fields = self._decode_chunk(
                        db.pop("__packed__"),
                        refs,
                        spec=spec,
                        names=tuple(names),
                        geoms=geoms,
                        rle_groups=rle_groups,
                    )
                self._pin_superbatch(fields)
                db["_meta"] = rests
                db.update(fields)
                trace_stamp_batch(db, "decode")
                yield db
                continue
            if plan is not None:
                names, spec, rest, refs, geoms, rle_groups = plan
                with metrics.span("decode.dispatch"):
                    fields = self._decode(
                        db.pop("__packed__"),
                        refs,
                        spec=spec,
                        names=tuple(names),
                        geoms=geoms,
                        rle=rle_groups,
                    )
                # The packed buffer travels unsharded, so on a multi-
                # device mesh the unpacked fields must be moved to their
                # configured shardings (async reshard; a no-op when the
                # pipeline simplified the sharding away on one device).
                for k, v in fields.items():
                    s = self._field_sharding(k)
                    if s is not None and getattr(v, "ndim", 0) >= len(
                        getattr(s, "spec", ()) or ()
                    ):
                        fields[k] = jax.device_put(v, s)
                db.update(rest)
                db.update(fields)
                trace_stamp_batch(db, "decode")
            yield db


class StreamDataPipeline:
    """End-to-end convenience: addresses -> device batches.

    The blendjax answer to ``DataLoader(RemoteIterableDataset(...))``
    (reference ``examples/datagen/minimal.py:16-22``): construct with the
    producer addresses and iterate sharded device batches.
    """

    def __init__(
        self,
        addresses,
        batch_size: int,
        schema=None,
        sharding=None,
        prefetch: int = 2,
        multihost: bool | None = None,
        mesh=None,
        data_axis: str = "data",
        launcher=None,
        chunk: int = 1,
        chunk_strict: bool = False,
        emit_packed: bool = False,
        ingest_workers: int = 1,
        emit_partial_final: bool = False,
        pad_partial: bool = True,
        place_in_driver: bool = False,
        defer_rle: bool | None = None,
        inflate_workers: int = 2,
        **stream_kwargs,
    ):
        from blendjax.data.stream import RemoteStream

        # With a launcher attached, a receive timeout becomes a producer
        # health check: dead instances raise with their exit codes (or are
        # respawned when the launcher has respawn=True) instead of an
        # opaque timeout (SURVEY.md §5 failure detection).
        self.launcher = launcher
        self._auto_timeout = (
            launcher is not None and "on_timeout" not in stream_kwargs
        )
        self._launcher_lock = threading.Lock()
        if self._auto_timeout:
            stream_kwargs["on_timeout"] = self._launcher_on_timeout()
        # ingest_workers > 1 shards the producer fleet across a pool of
        # receive/decode threads (blendjax.data.shard_ingest); 1 — the
        # default — is the existing single-thread HostIngest, ordering
        # and recording-tee semantics unchanged.
        self.ingest_workers = max(1, int(ingest_workers))
        self.emit_partial_final = bool(emit_partial_final)
        # inflate_workers: size of the sharded ingest pool's shared
        # zlib-inflate executor (decode-ahead in each shard stream;
        # docs/performance.md lever 2). Only engaged with
        # ingest_workers > 1; 0 disables.
        self.inflate_workers = max(0, int(inflate_workers))
        # place_in_driver: skip the feeder stage entirely — the
        # pipeline yields HOST batches (with their decode plans) and
        # the TrainDriver commits the grouped device_put at submit
        # time (TrainDriver(place=pipe.feeder.place)), so the transfer
        # overlaps the in-flight steps the driver ring tracks and the
        # one-dispatch contract covers placement too
        # (docs/performance.md lever 3). Requires the packed fused
        # path: every non-fused plan dispatches decode jits on what
        # device_stage yields, which would here still be host batches.
        self.place_in_driver = bool(place_in_driver)
        if place_in_driver and not emit_packed:
            raise ValueError(
                "place_in_driver=True requires emit_packed=True: "
                "placement folds into the fused train dispatch "
                "(make_fused_tile_step + TrainDriver(place=...))"
            )
        # defer_rle: leave "ndr" wire frames of prebatched messages
        # packed for in-jit expansion (docs/wire-protocol.md). Default:
        # exactly when the fused path consumes them (emit_packed).
        self.defer_rle = (
            bool(emit_packed) if defer_rle is None else bool(defer_rle)
        )
        if self.defer_rle:
            stream_kwargs.setdefault("defer_rle", True)
        # Shape-bucketed partials (on by default): a `_partial=True`
        # tail batch is zero-padded on the HOST up to a power-of-two
        # bucket with a `_mask` validity vector (pad_to_bucket), so a
        # finite stream's ragged tail hits a small fixed compile set
        # instead of recompiling the jitted step mid-run. The train-
        # layer losses are mask-aware (rows weighted by _mask, mean
        # divided by its sum), so the padded batch trains identically.
        # pad_partial=False restores the exact-shape tail.
        self.pad_partial = bool(pad_partial)
        self._addresses = None
        self._stream_kwargs = dict(stream_kwargs)
        if hasattr(addresses, "__iter__") and not isinstance(
            addresses, (list, tuple, str)
        ):
            # Any message-dict iterable works as a source (e.g. a
            # ReplayStream replaying a recording with no producers).
            self.stream = addresses
        else:
            self._addresses = (
                [addresses] if isinstance(addresses, str) else list(addresses)
            )
            if self.ingest_workers > 1 and (
                "worker_index" in stream_kwargs
                or "num_workers" in stream_kwargs
            ):
                # Both features split max_items/recording files by
                # worker slot; combined they'd double-split silently.
                raise ValueError(
                    "ingest_workers > 1 cannot be combined with explicit "
                    "worker_index/num_workers stream kwargs: the shard "
                    "pool owns the worker slots"
                )
            self.stream = RemoteStream(self._addresses, **stream_kwargs)
        self.ingest = None
        self.batch_size = batch_size
        self.schema = schema
        self.prefetch = prefetch
        # Mesh mode (the one-liner for the multi-chip live pipeline,
        # docs/performance.md "Going multi-chip"): derive the batch
        # sharding from the named mesh and let multihost follow the
        # process count — exactly what the DeviceFeeder does, resolved
        # ONCE here so the tile decoder sees the same layout.
        if mesh is not None:
            from blendjax.parallel.sharding import (
                batch_sharding,
                leading_shard_count,
            )

            if sharding is None:
                sharding = batch_sharding(mesh, axis=data_axis)
            axis_total = leading_shard_count(sharding)
            if axis_total > 1 and batch_size % axis_total:
                raise ValueError(
                    f"batch_size={batch_size} must divide evenly over "
                    f"the {axis_total}-way batch axis of mesh "
                    f"{dict(mesh.shape)} — every chip takes an equal "
                    "shard of each global batch"
                )
        self.mesh = mesh
        if multihost is None:
            multihost = (
                mesh is not None and _require_jax().process_count() > 1
            )
        if emit_packed and multihost:
            # The packed single-buffer form cannot shard (bytes, not
            # batch): multihost tile batches are decoded via global-array
            # assembly instead, so there is nothing packed to emit and
            # make_fused_tile_step would mis-consume the decoded batches.
            raise NotImplementedError(
                "emit_packed=True is incompatible with multihost=True — "
                "multihost tile streams decode via global-array assembly "
                "(use the regular decode-then-step path)"
            )
        if self.place_in_driver and multihost:
            raise NotImplementedError(
                "place_in_driver=True is single-host: multihost batches "
                "must assemble global arrays in the feeder"
            )
        # Single-device shardings are stripped ONCE here so every stage
        # below (feeder placement, tile ref placement, decoded-field
        # resharding) sees the same simplified value and none pays the
        # explicit-sharding slow path on a 1-device mesh.
        sharding = DeviceFeeder._simplify(sharding)
        # chunk>1 disables the transfer throttle: chunk grouping already
        # cuts transfer count K-fold, and a throttle block waits behind
        # ALL queued compute.
        self.feeder = DeviceFeeder(
            sharding=sharding, prefetch=prefetch, multihost=multihost,
            throttle=0 if chunk > 1 else 8,
        )
        self.tiles = TileStreamDecoder(
            sharding=sharding, multihost=multihost, chunk=chunk,
            chunk_strict=chunk_strict, emit_packed=emit_packed,
        )

    def _launcher_on_timeout(self):
        """One launcher-health timeout hook with its OWN retry budget —
        the sharded pool hands a fresh closure to every shard so one
        slow producer can't burn its peers' retries, and assert_alive
        (not written for concurrent callers) is serialized across the
        worker threads."""
        launcher = self.launcher
        retries = {"left": 3}

        def on_timeout():
            with self._launcher_lock:
                # Deliberate: this hook only runs once the stream has
                # ALREADY stalled (recv timeout), so a bounded liveness
                # check costs no throughput; serialized behind
                # _launcher_lock across shards.
                # bjx: ignore[BJX110]
                launcher.assert_alive()  # raises (or respawns) as configured
            # All producers alive but silent: retry a bounded number of
            # times (covers slow startup/respawn), then fail fast.
            retries["left"] -= 1
            return retries["left"] >= 0

        return on_timeout

    @classmethod
    def from_recording(cls, source, batch_size: int, loop: bool = False,
                       allow_pickle: bool = False, **kwargs):
        """Replay a ``.bjr`` recording (path, path list, or prefix)
        through the full device pipeline — tile-delta recordings decode
        to bit-exact frames exactly like live traffic (the reference can
        only replay into torch datasets, ``dataset.py:119-153``).

        Untrusted-safe by default: pickle-bearing recordings (legacy
        ``.btr``, or ``.bjr`` teed from pickle-codec producers) need an
        explicit ``allow_pickle=True``."""
        from blendjax.data.replay import ReplayStream

        return cls(
            ReplayStream(source, allow_pickle=allow_pickle, loop=loop),
            batch_size=batch_size,
            **kwargs,
        )

    def __iter__(self):
        from blendjax.data.batcher import HostIngest

        shards = None
        if self.ingest_workers > 1:
            from blendjax.data.stream import partition_addresses

            if self._addresses is None:
                logger.warning(
                    "ingest_workers=%d requested but the source is an "
                    "opaque iterable (not producer addresses): falling "
                    "back to single-threaded ingest",
                    self.ingest_workers,
                )
            else:
                shards = partition_addresses(
                    self._addresses, self.ingest_workers
                )
                if len(shards) < 2:
                    shards = None  # one producer: nothing to parallelize
                    logger.warning(
                        "ingest_workers=%d requested but only one "
                        "producer address is available: falling back to "
                        "single-threaded ingest",
                        self.ingest_workers,
                    )
        if shards is not None:
            from blendjax.data.shard_ingest import ShardedHostIngest
            from blendjax.data.stream import RemoteStream

            def shard_stream(i, shard):
                kwargs = dict(self._stream_kwargs)
                # max_items is enforced GLOBALLY by the pool (shards see
                # disjoint producer subsets — an even per-shard split
                # would block one shard on messages only another shard's
                # producers hold).
                kwargs.pop("max_items", None)
                if self._auto_timeout:
                    # fresh closure per shard: independent retry budgets
                    kwargs["on_timeout"] = self._launcher_on_timeout()
                # enable_recording() mutates self.stream after
                # construction — carry the tee into the shard streams
                # (worker-indexed files), matching the single path.
                prefix = getattr(self.stream, "record_path_prefix", None)
                if prefix is not None:
                    kwargs["record_path_prefix"] = prefix
                    kwargs["record_max_messages"] = (
                        self.stream.record_max_messages
                    )
                return RemoteStream(
                    shard, worker_index=i, num_workers=len(shards),
                    # shards see DISJOINT producer subsets (whole
                    # per-producer streams), so seq-gap accounting is
                    # sound despite the worker slot — override the
                    # auto num_workers==1 default.
                    track_gaps=True,
                    **kwargs,
                )

            self.ingest = ShardedHostIngest(
                [shard_stream(i, s) for i, s in enumerate(shards)],
                batch_size=self.batch_size,
                schema=self.schema,
                prefetch=self.prefetch,
                emit_partial_final=self.emit_partial_final,
                max_messages=self._stream_kwargs.get("max_items"),
                inflate_workers=self.inflate_workers,
            )
        else:
            self.ingest = HostIngest(
                self.stream,
                batch_size=self.batch_size,
                schema=self.schema,
                prefetch=self.prefetch,
                emit_partial_final=self.emit_partial_final,
            )
        self.ingest.start()
        self.tiles.reset()
        source = (
            self._pad_partial_stage(self.ingest)
            if self.pad_partial else self.ingest
        )
        host = self.tiles.host_stage(source)
        if self.place_in_driver:
            # No feeder stage: device_stage only attaches the fused
            # decode plans here (emit_packed — enforced at
            # construction), so the yielded batches are HOST dicts and
            # the TrainDriver commits the one grouped placement at
            # submit time (TrainDriver(place=pipe.feeder.place)).
            return iter(self.tiles.device_stage(host))
        return iter(self.tiles.device_stage(self.feeder(host)))

    def _pad_partial_stage(self, batches):
        """Bucket-pad `_partial` tail batches on the host (numpy, free)
        before tile handling and device placement, so every downstream
        stage — packing, feeder sharding, the jitted step — sees a
        regular bucket shape plus a `_mask` validity vector.

        On a mesh, buckets are restricted to multiples of the batch
        axis's shard count: a 3-row tail padded to the default bucket
        4 cannot be placed under an 8-way ``data`` sharding (device_put
        rejects the split), so the ladder starts at the shard count —
        every padded tail still places in one call like a full batch."""
        from blendjax.data.batcher import bucket_sizes, pad_to_bucket

        buckets = None
        sharding = _representative_sharding(self.feeder.sharding)
        if sharding is not None:
            from blendjax.parallel.sharding import leading_shard_count

            ways = leading_shard_count(sharding)
            if ways > 1:
                # non-empty: the constructor enforced batch_size % ways
                buckets = tuple(
                    b for b in bucket_sizes(self.batch_size)
                    if b % ways == 0
                )
        for hb in batches:
            if hb.get("_partial"):
                hb = pad_to_bucket(
                    hb, batch_size=self.batch_size, buckets=buckets
                )
            yield hb

    def queue_depth(self) -> int:
        return 0 if self.ingest is None else self.ingest.queue_depth()

    # -- elastic membership ---------------------------------------------------

    def connect(self, addr: str) -> None:
        """Admit one producer endpoint mid-run (fleet controller /
        remote admission): forwarded to the sharded ingest pool when
        one is live, else to the underlying stream. Address
        bookkeeping keeps re-iterations consistent."""
        if self._addresses is not None and addr not in self._addresses:
            self._addresses.append(addr)
        target = self.ingest if hasattr(self.ingest, "connect") else self.stream
        connect = getattr(target, "connect", None)
        if connect is None:
            raise RuntimeError(
                "this pipeline's source does not support runtime "
                "membership (opaque iterable / replay)"
            )
        connect(addr)

    def disconnect(self, addr: str) -> None:
        """Retire one producer endpoint mid-run. Drain first: retire
        the producer, keep receiving through a grace window, THEN
        disconnect — zmq drops messages still queued on the pipe."""
        if self._addresses is not None and addr in self._addresses:
            self._addresses.remove(addr)
        target = self.ingest if hasattr(self.ingest, "disconnect") else self.stream
        disconnect = getattr(target, "disconnect", None)
        if disconnect is not None:
            disconnect(addr)

    def doctor(self, driver=None):
        """One-line bottleneck verdict for the live pipeline
        (:mod:`blendjax.obs.doctor`): classifies producer-/wire-/
        decode-/feed-/step-bound from the current metrics snapshot plus
        frame lineage. ``driver`` may be a ``TrainDriver`` (or its
        ``stats`` dict) so ring-full blocks feed the diagnosis; the
        pipeline's own ``prefetch`` bound lets the queue-depth
        high-water gauge count as backpressure evidence.

        >>> print(pipe.doctor().render())
        """
        from blendjax.obs import diagnose_current

        stats = getattr(driver, "stats", driver)
        metrics.gauge("ingest.queue_depth", self.queue_depth())
        return diagnose_current(driver=stats, prefetch=self.prefetch)

    def stop(self):
        try:
            if self.ingest is not None:
                self.ingest.stop()
        except RuntimeError:
            # A wedged ingest thread (e.g. an opaque source blocked with
            # no timeout) must not mask a with-body exception in
            # __exit__ or skip the stream cleanup below — the threads
            # are daemons; log the diagnosis and keep tearing down.
            logger.exception("ingest did not shut down cleanly")
        finally:
            close = getattr(self.stream, "close", None)
            if close is not None:  # e.g. ReplayStream's recording handles
                close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
