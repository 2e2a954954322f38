"""Device ledger: XLA cost/memory accounting, collective-bytes
breakdowns, live HBM gauges, and a retrace audit.

Every other observability layer watches the *host side* of the pipeline
(spans, lineage, traces, doctor); the device itself was a black box —
MFU needed a hand-fed ``flops_per_image``, HBM usage was invisible
until an OOM, and a retrace storm only showed up as mysteriously slow
steps. This module is the missing device half, in three pieces:

1. **Compile-time accounting** — :class:`ExecutableLedger` extracts,
   per compiled step signature, XLA's own ``cost_analysis()`` (flops,
   bytes accessed) and ``memory_analysis()`` (argument / output / temp
   / generated-code bytes), and parses the HLO text for a
   per-collective byte breakdown (all-reduce / all-gather /
   reduce-scatter / collective-permute / all-to-all, attributed to the
   mesh axis whose size matches the replica group). Registration is
   wired into :func:`blendjax.train.aot.build_aot_step` (free — the
   executables already exist) and ``TrainDriver.build()`` /
   ``MeshTrainDriver.build()``, and publishes the ``device.*`` gauge
   family the exporters, reporter JSONL, and bench stage breakdowns
   all carry. The cost-model FLOPs replace the hand-fed
   ``flops_per_image`` MFU path when available (hand-fed stays as the
   override).
2. **Runtime HBM gauges** — :meth:`ExecutableLedger.poll_memory` reads
   ``device.memory_stats()`` each reporter tick into
   ``device.hbm_in_use_bytes`` / headroom gauges the SLO watchdog can
   rule on (``gauge(device.hbm_headroom_frac) >= 0.1``). Backends
   without memory stats (CPU) degrade to a silent no-op.
3. **Retrace audit** — :class:`RetraceAudit` watches a jitted step's
   dispatch-cache size per dispatch; growth past the warm-up window
   counts ``device.retraces``, attributes the offending batch
   signature, and can trip a flight-recorder dump. The doctor's
   ``retrace-storm`` and ``memory-bound`` verdicts read these signals.

Failure policy: every extraction is guarded independently — a jax
version whose ``cost_analysis()`` returns ``None``, a backend whose
``memory_analysis()`` raises, an HLO dialect the parser doesn't know —
the ledger records the field as ``"unavailable"`` (and counts
``device.ledger_failures``) but NEVER raises into the driver or the
reporter thread. Like the rest of :mod:`blendjax.obs` the module is
import-cheap: jax is imported lazily inside the functions that need
it, so producer processes can import the package without it.
"""

from __future__ import annotations

import logging
import re
import threading

from blendjax.utils.metrics import Metrics, metrics

logger = logging.getLogger(__name__)

__all__ = [
    "COLLECTIVE_KINDS",
    "ExecutableLedger",
    "RetraceAudit",
    "V5E_PEAK_FLOPS",
    "batch_signature",
    "chip_peak_flops",
    "default_peak_flops",
    "ledger",
    "measure_model_flops",
    "parse_collectives",
]

UNAVAILABLE = "unavailable"

# Peak dense bf16 throughput of one TPU v5e chip (197 TFLOP/s, Google
# Cloud documentation "TPU v5e").
V5E_PEAK_FLOPS = 197e12

#: Peak dense bf16 FLOP/s of one chip, keyed by ``device_kind`` exactly
#: as JAX reports it (``jax.devices()[0].device_kind``), with the label
#: the public spec sheet uses. The ``TrainDriver`` MFU gauge takes its
#: ``peak_flops`` denominator from here; an accelerator that is not in
#: the table is an error (:func:`chip_peak_flops`), never a guess.
CHIP_PEAK_FLOPS = {
    "TPU v5 lite": (V5E_PEAK_FLOPS, "TPU v5e"),
    "TPU v6 lite": (918e12, "TPU v6e"),
    "TPU v4": (275e12, "TPU v4"),
    "TPU v3": (123e12, "TPU v3"),
}

#: Collective kinds the HLO parser attributes, in HLO spelling.
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

#: Per-kind byte gauges, index-aligned with :data:`COLLECTIVE_KINDS`
#: (constant names so the BJX123 contract gate can enumerate them).
COLLECTIVE_METRICS = (
    "device.collective.all_reduce_bytes",
    "device.collective.all_gather_bytes",
    "device.collective.reduce_scatter_bytes",
    "device.collective.collective_permute_bytes",
    "device.collective.all_to_all_bytes",
)

#: Compile-time accounting gauges published by
#: :meth:`ExecutableLedger._publish`, index-aligned with
#: :data:`_ENTRY_FIELDS` below (constant names so the BJX123 contract
#: gate can enumerate the family — docs/observability.md "device.*").
LEDGER_GAUGES = (
    "device.flops_per_step",
    "device.bytes_accessed",
    "device.hbm_peak_bytes",
    "device.temp_bytes",
    "device.argument_bytes",
    "device.output_bytes",
    "device.generated_code_bytes",
    "device.collective_bytes",
)

#: Entry-dict fields feeding :data:`LEDGER_GAUGES`, same order.
_ENTRY_FIELDS = (
    "flops",
    "bytes_accessed",
    "hbm_peak_bytes",
    "temp_bytes",
    "argument_bytes",
    "output_bytes",
    "generated_code_bytes",
    "collective_bytes",
)

#: Runtime HBM gauges from :meth:`ExecutableLedger.poll_memory`
#: (absent on backends without ``memory_stats()``, e.g. CPU).
HBM_GAUGES = (
    "device.hbm_in_use_bytes",
    "device.hbm_peak_in_use_bytes",
    "device.hbm_limit_bytes",
    "device.hbm_headroom_frac",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# One HLO instruction line: "%name = <result types> <op>(...)". The
# result segment may be a tuple for async-start forms; every
# dtype[dims] token inside it is summed. "-done" forms are skipped —
# their bytes were counted on the paired "-start".
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s*(?P<result>\(?[a-z0-9_]+\[[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?P<variant>-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
# replica_groups=[G,S]<=[N] (iota form) or replica_groups={{0,1},...}
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * width
    return total


def parse_collectives(hlo_text: str, mesh_axes: dict | None = None) -> dict:
    """Per-collective byte breakdown of one HLO module's text.

    Returns ``{"total_bytes", "ops", "per_kind": {kind: bytes},
    "per_axis": {axis: bytes}}``. Bytes are the result-shape bytes of
    each collective instruction — for an all-reduce that is exactly the
    reduced payload (the data-parallel grad sync's param bytes x policy
    dtype width), which is the figure layout choices are made on.

    ``mesh_axes`` (``{axis_name: size}`` — pass ``dict(mesh.shape)``)
    attributes each op to the mesh axis whose size matches its replica
    group size; group sizes matching no axis (or more than one) land
    under ``"unknown"``/the joined names. Parse failures raise —
    callers hold the never-raise contract (:class:`ExecutableLedger`
    wraps this in its guarded extraction).
    """
    per_kind = {k: 0 for k in COLLECTIVE_KINDS}
    per_axis: dict = {}
    ops = 0
    for m in _COLLECTIVE_LINE_RE.finditer(hlo_text):
        if m.group("variant") == "-done":
            continue
        nbytes = _shape_bytes(m.group("result"))
        if not nbytes:
            continue
        ops += 1
        per_kind[m.group("op")] += nbytes
        if mesh_axes:
            line = hlo_text[m.end():m.end() + 400].split("\n", 1)[0]
            group = None
            gm = _GROUPS_IOTA_RE.search(line)
            if gm:
                group = int(gm.group(2))
            else:
                gm = _GROUPS_LIST_RE.search(line)
                if gm:
                    group = len([
                        v for v in gm.group(1).split(",") if v.strip()
                    ])
            axes = [
                name for name, size in mesh_axes.items()
                if group is not None and int(size) == group
            ]
            axis = "|".join(axes) if axes else "unknown"
            per_axis[axis] = per_axis.get(axis, 0) + nbytes
    return {
        "total_bytes": sum(per_kind.values()),
        "ops": ops,
        "per_kind": per_kind,
        "per_axis": per_axis,
    }


def batch_signature(batch: dict) -> tuple:
    """The dispatch signature the retrace audit attributes: sorted
    (field, shape, dtype) over the array fields (same universe as
    ``blendjax.train.aot._signature`` — ``_mask`` plus every
    non-underscore leading-dim field). Shape reads only, no numpy."""
    items = []
    for k in sorted(batch):
        v = batch[k]
        if k.startswith("_") and k != "_mask":
            continue
        shape = tuple(getattr(v, "shape", ()) or ())
        if not shape and k != "_mask":
            continue
        items.append((k, shape, str(getattr(v, "dtype", ""))))
    return tuple(items)


def chip_peak_flops(device_kind: str) -> tuple:
    """``(peak_flops, chip_label)`` for a ``device_kind``; raises
    ``KeyError`` naming the kind when the table does not hold it."""
    try:
        return CHIP_PEAK_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {device_kind!r} — "
            "add it to blendjax.obs.devledger.CHIP_PEAK_FLOPS with its "
            f"source (known: {sorted(CHIP_PEAK_FLOPS)})"
        ) from None


def default_peak_flops() -> tuple | None:
    """``(peak_flops, chip_label)`` of the chip this process runs on, or
    ``None`` on the CPU backend (no chip, no utilization). An accelerator
    missing from :data:`CHIP_PEAK_FLOPS` raises (:func:`chip_peak_flops`)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    return chip_peak_flops(jax.devices()[0].device_kind)


# -- the cost-model FLOPs probe -------------------------------------------------

#: Memo for :func:`measure_model_flops`, keyed by (model class, shape,
#: batch) so a run pays one extra lowering per model/geometry.
_FLOPS_MEMO: dict = {}


def measure_model_flops(model=None, loss_fn=None,
                        label: str = "CubeRegressor fwd+bwd",
                        shape=(480, 640), batch: int = 8,
                        memo: bool = True) -> dict:
    """Fwd+bwd FLOPs per image of the supervised step, from the
    compiled executable's own cost analysis (XLA's count, not a hand
    estimate). The one home for the cost-model path: the driver builds
    derive ``flops_per_image`` from the same figure via the ledger.

    Always lowers the UNCHUNKED per-batch step: the per-image math is
    identical at any chunk, and XLA's cost model counts a ``lax.scan``
    body ONCE regardless of trip count, so the chunked program would
    under-report per-image FLOPs by ~chunk (verified on this backend).
    """
    import numpy as np

    from blendjax.models import CubeRegressor
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import make_supervised_step, make_train_state

    key = (
        type(model).__name__ if model is not None else "CubeRegressor",
        tuple(shape), int(batch),
        getattr(loss_fn, "__name__", None) if loss_fn else None,
    )
    if memo and key in _FLOPS_MEMO:
        return dict(_FLOPS_MEMO[key])
    mesh = create_mesh({"data": -1})
    state = make_train_state(
        CubeRegressor() if model is None else model,
        np.zeros((batch, *shape, 4), np.uint8), mesh=mesh,
    )
    step = make_supervised_step(
        mesh=mesh, batch_sharding=batch_sharding(mesh), loss_fn=loss_fn
    )
    sb = {
        "image": np.zeros((batch, *shape, 4), np.uint8),
        "xy": np.zeros((batch, 8, 2), np.float32),
    }
    ca = step.lower(state, sb).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    flops = float(ca["flops"])
    out = {
        "flops_per_image": round(flops / batch),
        "model": label,
        "source": "compiled.cost_analysis() (unchunked step)",
        "chip": "TPU v5e",
        "peak_flops": V5E_PEAK_FLOPS,
    }
    if memo:
        _FLOPS_MEMO[key] = dict(out)
    return out


# -- the ledger ----------------------------------------------------------------


def _sig_lead(signature) -> int | None:
    """Leading batch dim of a registered signature (max over the
    non-mask fields' first dims) — what turns per-step FLOPs into
    per-image."""
    leads = [
        shape[0] for name, shape, _dt in (signature or ())
        if name != "_mask" and shape
    ]
    return max(leads) if leads else None


class ExecutableLedger:
    """Per-signature device accounting plus the runtime HBM poll and
    retrace event log. One process-wide instance (:data:`ledger`)
    mirrors everything into the ``device.*`` registry family; the full
    structured view (:meth:`report`) rides flight bundles as
    ``device_ledger.json`` and the bench ``live_device_ledger`` row.
    """

    def __init__(self, registry: Metrics = metrics):
        self.registry = registry
        self._lock = threading.Lock()
        self._entries: list = []
        self._retraces: list = []
        self._memory: dict | None = None
        self._flight = None
        self._flight_threshold = 3
        self._flight_fired = False

    # -- compile-time registration --------------------------------------------

    def register(self, name: str, compiled, signature=None,
                 mesh=None) -> dict:
        """Extract cost/memory/collective accounting from one compiled
        executable (``jit(...).lower(...).compile()`` result). Every
        field is guarded independently; failures record
        ``"unavailable"`` and count ``device.ledger_failures`` — this
        never raises into a driver build."""
        entry: dict = {
            "name": name,
            "signature": repr(signature) if signature is not None else None,
            "batch_images": _sig_lead(signature),
        }
        failures = 0

        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) and ca else ca
            if not isinstance(ca, dict) or "flops" not in ca:
                raise ValueError(f"no flops in cost analysis: {type(ca)}")
            entry["flops"] = float(ca["flops"])
            entry["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        except Exception:
            entry["flops"] = entry["bytes_accessed"] = UNAVAILABLE
            failures += 1
            logger.debug("cost_analysis unavailable for %s", name,
                         exc_info=True)

        try:
            ma = compiled.memory_analysis()
            arg = int(ma.argument_size_in_bytes)
            out = int(ma.output_size_in_bytes)
            temp = int(ma.temp_size_in_bytes)
            gen = int(ma.generated_code_size_in_bytes)
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            entry.update(
                argument_bytes=arg, output_bytes=out, temp_bytes=temp,
                generated_code_bytes=gen, alias_bytes=alias,
                # donated/aliased buffers are counted once: they are the
                # same HBM on both sides of the step
                hbm_peak_bytes=max(arg + out + temp + gen - alias, 0),
            )
        except Exception:
            for k in ("argument_bytes", "output_bytes", "temp_bytes",
                      "generated_code_bytes", "hbm_peak_bytes"):
                entry[k] = UNAVAILABLE
            failures += 1
            logger.debug("memory_analysis unavailable for %s", name,
                         exc_info=True)

        try:
            axes = None
            if mesh is not None:
                axes = dict(mesh) if isinstance(mesh, dict) else {
                    ax: int(n)
                    for ax, n in zip(mesh.axis_names, mesh.devices.shape)
                }
            entry["collectives"] = parse_collectives(
                compiled.as_text(), mesh_axes=axes
            )
        except Exception:
            entry["collectives"] = UNAVAILABLE
            failures += 1
            logger.debug("HLO collective parse failed for %s", name,
                         exc_info=True)

        if failures:
            self.registry.count("device.ledger_failures", failures)
        with self._lock:
            self._entries.append(entry)
        self._publish(entry)
        return entry

    def register_aot_set(self, name: str, compiled: dict,
                         mesh=None) -> list:
        """Register every signature of an AOT-compiled step set
        (``{signature: executable}`` — what :func:`build_aot_step`
        holds). The LAST published entry wins the point-in-time
        ``device.*`` gauges; register the steady-state (full-batch)
        signature last for the headline numbers — ``build_aot_step``'s
        spec order already does (full batch first is re-published by
        :meth:`_publish` largest-lead-last below)."""
        entries = []
        items = sorted(
            compiled.items(),
            key=lambda kv: (_sig_lead(kv[0]) or 0),
        )
        for sig, exe in items:
            entries.append(
                self.register(name, exe, signature=sig, mesh=mesh)
            )
        return entries

    def register_step(self, name: str, step, state, example_batch: dict,
                      mesh=None) -> dict | None:
        """Lower + compile a jitted step once purely for accounting
        (the non-AOT path, where no executable exists at build time),
        then register it. With the persistent compilation cache
        configured the first real dispatch is then served from disk.
        Guarded end to end — returns ``None`` on any failure."""
        try:
            import jax
            import numpy as np

            def _abs(x):
                if not hasattr(x, "dtype"):
                    return x
                return jax.ShapeDtypeStruct(
                    np.shape(x), x.dtype,
                    sharding=getattr(x, "sharding", None),
                )

            fields = {
                k: v for k, v in example_batch.items()
                if k == "_mask"
                or (not k.startswith("_") and getattr(v, "ndim", 0) >= 1)
            }
            sig = tuple(sorted(
                (k, tuple(np.shape(v)), str(np.dtype(v.dtype)))
                for k, v in fields.items()
            ))
            compiled = step.lower(
                jax.tree_util.tree_map(_abs, state),
                jax.tree_util.tree_map(_abs, fields),
            ).compile()
        except Exception:
            self.registry.count("device.ledger_failures")
            logger.debug("ledger step registration failed for %s", name,
                         exc_info=True)
            return None
        return self.register(name, compiled, signature=sig, mesh=mesh)

    def _publish(self, entry: dict) -> None:
        """Mirror one entry into the ``device.*`` gauges (last
        registration wins — the gauges are the live view; the entry
        list is the per-signature history)."""
        g = self.registry.gauge
        col = entry.get("collectives")
        values = dict(entry)
        if isinstance(col, dict):
            values["collective_bytes"] = col["total_bytes"]
        for field, metric in zip(_ENTRY_FIELDS, LEDGER_GAUGES):
            v = values.get(field)
            # "unavailable" extraction failures stay out of the gauges
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                g(metric, v)
        if isinstance(col, dict):
            for kind, metric in zip(COLLECTIVE_KINDS, COLLECTIVE_METRICS):
                g(metric, col["per_kind"].get(kind, 0))

    # -- cost-model MFU hand-off ----------------------------------------------

    def flops_per_image(self, batch_images: int | None = None) -> float | None:
        """Cost-model FLOPs per image from the newest matching entry:
        the figure ``TrainDriver.build`` feeds the ``train.mfu`` gauge
        when no hand-fed ``flops_per_image`` override is given.
        ``batch_images`` selects the signature whose lead matches (the
        steady-state full batch); without it the largest-lead entry
        wins."""
        with self._lock:
            entries = [
                e for e in self._entries
                if isinstance(e.get("flops"), float) and e["batch_images"]
            ]
        if not entries:
            return None
        if batch_images:
            match = [e for e in entries if e["batch_images"] == batch_images]
            entries = match or entries
        e = max(entries, key=lambda e: e["batch_images"])
        return e["flops"] / e["batch_images"]

    # -- runtime HBM poll -----------------------------------------------------

    def poll_memory(self, registry: Metrics | None = None) -> dict | None:
        """One ``device.memory_stats()`` sample across the local
        devices, published as gauges (in-use / peak / limit / headroom
        fraction, worst device wins the headroom). Returns the sample,
        or ``None`` where the backend has no memory stats (CPU) — a
        graceful no-op, never an exception into the reporter tick."""
        reg = registry or self.registry
        try:
            import jax

            per_device = []
            for dev in jax.local_devices():
                stats = dev.memory_stats()
                if not stats:
                    continue
                per_device.append({
                    "device": str(dev),
                    "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(
                        stats.get("peak_bytes_in_use", 0)
                    ),
                    "bytes_limit": int(stats.get("bytes_limit", 0)),
                })
        except Exception:
            logger.debug("memory_stats poll failed", exc_info=True)
            return None
        if not per_device:
            with self._lock:
                self._memory = {"supported": False}
            return None
        in_use = max(d["bytes_in_use"] for d in per_device)
        peak = max(d["peak_bytes_in_use"] for d in per_device)
        limit = max(d["bytes_limit"] for d in per_device)
        sample = {
            "supported": True,
            "bytes_in_use": in_use,
            "peak_bytes_in_use": peak,
            "bytes_limit": limit,
            "devices": per_device,
        }
        in_use_gauge, peak_gauge, limit_gauge, headroom_gauge = HBM_GAUGES
        reg.gauge(in_use_gauge, in_use)
        reg.gauge(peak_gauge, peak)
        if limit:
            reg.gauge(limit_gauge, limit)
            headroom = min(
                1.0 - d["bytes_in_use"] / d["bytes_limit"]
                for d in per_device if d["bytes_limit"]
            )
            headroom = round(max(headroom, 0.0), 4)
            reg.gauge(headroom_gauge, headroom)
            sample["headroom_frac"] = headroom
        with self._lock:
            self._memory = sample
        return sample

    # -- retrace events -------------------------------------------------------

    def note_retrace(self, signature, count: int = 1,
                     cache_size: int | None = None) -> None:
        """Record ``count`` retraces attributed to ``signature``
        (called by :class:`RetraceAudit`); mirrors the
        ``device.retraces`` counter and arms the optional flight dump."""
        self.registry.count("device.retraces", count)
        with self._lock:
            self._retraces.append({
                "signature": repr(signature),
                "count": count,
                "cache_size": cache_size,
            })
            total = sum(r["count"] for r in self._retraces)
            flight = self._flight
            fire = (
                flight is not None and not self._flight_fired
                and total >= self._flight_threshold
            )
            if fire:
                self._flight_fired = True
        if fire:
            try:
                flight.dump(
                    reason=f"retrace-storm: {total} retraces "
                    f"(latest signature {signature!r})",
                    registry=self.registry,
                )
            except Exception:
                logger.exception("retrace flight dump failed")

    def attach_flight(self, recorder, threshold: int = 3) -> None:
        """Arm a one-shot :class:`~blendjax.obs.watchdog.FlightRecorder`
        dump once ``threshold`` total retraces accumulate (the
        ``StatsReporter`` wires its recorder here automatically)."""
        with self._lock:
            self._flight = recorder
            self._flight_threshold = max(1, int(threshold))
            self._flight_fired = False

    # -- views ----------------------------------------------------------------

    @property
    def retrace_count(self) -> int:
        with self._lock:
            return sum(r["count"] for r in self._retraces)

    def report(self) -> dict:
        """The full structured ledger: per-signature entries, retrace
        events with attribution, and the last HBM sample — the
        ``device_ledger.json`` payload."""
        with self._lock:
            return {
                "entries": [dict(e) for e in self._entries],
                "retraces": {
                    "count": sum(r["count"] for r in self._retraces),
                    "events": [dict(r) for r in self._retraces],
                },
                "memory": dict(self._memory) if self._memory else None,
            }

    def reset(self) -> None:
        """Drop entries/events (bench legs and tests; the registry's
        own ``device.*`` values are cleared by ``metrics.reset()``)."""
        with self._lock:
            self._entries.clear()
            self._retraces.clear()
            self._memory = None
            self._flight_fired = False


#: Process-wide ledger (the registry singleton's sibling).
ledger = ExecutableLedger()


class RetraceAudit:
    """Per-dispatch jit cache-size delta detection.

    ``observe(batch)`` after every dispatch compares the watched jit
    wrapper's dispatch-cache size against the last observation; growth
    past the ``warmup`` window counts ``device.retraces`` on the
    ledger with the offending batch signature attributed. The first
    ``warmup`` observations only move the baseline — legitimate
    warm-up compiles (including the donated-layout second compile of
    the same signature) never count.

    Never raises: a wrapper without ``_cache_size`` disables the audit
    (:attr:`active` False), and any polling error deactivates it.
    """

    def __init__(self, fn, warmup: int = 2,
                 ledger: ExecutableLedger = ledger):
        # unwrap the AOT set's fallback jit — precompiled dispatches
        # never touch the jit cache, so cache growth there IS the
        # unbucketed-shape signal
        inner = getattr(fn, "_step", fn)
        self._cache_size = getattr(inner, "_cache_size", None)
        self.active = callable(self._cache_size)
        self.warmup = max(0, int(warmup))
        self.ledger = ledger
        self._observed = 0
        self._last: int | None = None

    @classmethod
    def for_step(cls, fn, warmup: int = 2) -> "RetraceAudit | None":
        audit = cls(fn, warmup=warmup)
        return audit if audit.active else None

    def observe(self, batch) -> bool:
        """True when this dispatch grew the jit cache past warm-up."""
        if not self.active:
            return False
        try:
            size = int(self._cache_size())
        except Exception:
            self.active = False
            logger.debug("retrace audit disabled", exc_info=True)
            return False
        self._observed += 1
        grew = self._last is not None and size > self._last
        delta = size - (self._last or 0)
        self._last = size
        if not grew or self._observed <= self.warmup:
            return False
        try:
            self.ledger.note_retrace(
                batch_signature(batch), count=delta, cache_size=size,
            )
        except Exception:
            logger.debug("retrace attribution failed", exc_info=True)
        return True
