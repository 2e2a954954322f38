"""AOT step compilation with a persistent on-disk cache.

Cold start used to pay the full jit trace+compile of the step set on the
first batch of every shape in the bucket ladder — seconds of wall time the
checkpoint/elastic-resume and fleet subsystems re-pay on every restart.
This module closes that gap in two layers:

1. **AOT set** — :func:`build_aot_step` lowers the jitted step against
   abstract ``jax.ShapeDtypeStruct`` trees derived from the *concrete*
   state plus the ``pad_to_bucket`` ladder (every batch shape the driver
   can dispatch: the full batch unmasked, and each bucket size with its
   ``_mask``), and compiles all of them before step 0.  Dispatch then hits
   a precompiled executable keyed by the batch signature; an unseen shape
   falls back to the wrapped jit (counted ``train.aot_fallbacks``) so
   correctness never depends on the ladder being complete.
2. **Persistent cache** — :func:`configure_compilation_cache` points
   ``jax.config``'s compilation cache at a directory (thresholds zeroed so
   CPU-sized test steps persist too), and a keyed *manifest* over
   ``(model class, precision policy, mesh layout, decode plan, bucket
   ladder, jax version, backend)`` records which step signatures were
   compiled under that key — the warm/cold distinction behind the
   ``train.aot_cache_hits`` / ``train.aot_cache_misses`` counters and the
   CI-gated ``live_start`` warm-vs-cold ratio.

All compile wall time runs under the ``train.compile_ms`` span so the
doctor and bench stage breakdowns can tell a cold-start-dominated run from
a genuinely step-bound one.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import tempfile
import time

import jax
import numpy as np

from blendjax.data.batcher import bucket_sizes
from blendjax.obs.devledger import ledger
from blendjax.utils.metrics import metrics

logger = logging.getLogger(__name__)

__all__ = [
    "AotStepSet",
    "build_aot_step",
    "batch_specs_for_ladder",
    "configure_compilation_cache",
    "cache_key",
]

_MANIFEST = "aot_manifest.json"


# -- persistent cache wiring --------------------------------------------------

#: Where the compile cache goes when neither the environment nor the
#: caller names a place: ``<checkout>/.xla_cache`` (git-ignored). A fixed
#: path, because the path is part of what a cache entry is looked up by.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def configure_compilation_cache(cache_dir: str | None = None) -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    The one place the repo decides where compiled programs are kept:
    ``JAX_COMPILATION_CACHE_DIR`` wins whenever it is set (so a machine
    can place the cache from outside, and no other directory is ever set
    in code); otherwise ``cache_dir``; otherwise :data:`DEFAULT_CACHE_DIR`.

    Zeroes the min-compile-time / min-entry-size thresholds so small
    steps are persisted too (the defaults only cache "expensive"
    compiles), and resets the cache so the settings take effect even
    after something already compiled in this process.
    """
    from jax.experimental.compilation_cache import compilation_cache

    resolved = (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or cache_dir
        or DEFAULT_CACHE_DIR
    )
    os.makedirs(resolved, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", resolved)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # without this the CPU backend never writes cache entries at all
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    # JAX latches the cache state on the first compile of the process: if
    # anything compiled before the dir was set (state init always does),
    # the "no cache" decision sticks. Resetting re-reads the config.
    compilation_cache.reset_cache()
    return resolved


def cache_key(
    *,
    model: object = None,
    precision: object = None,
    mesh: object = None,
    decode_plan: object = None,
    buckets: tuple | list | None = None,
    layout: object = None,
    rules: tuple | list | None = None,
) -> str:
    """Stable manifest key over everything that invalidates compiled steps.

    Anatomy (see docs/performance.md): model class qualname, precision
    policy, mesh layout (axis names x sizes), the named Layout + its
    partition-rule set (two rule sets on the SAME mesh are different
    programs), decode plan, bucket ladder, plus the JAX version and
    backend — change any one and the key moves, so a stale cache can
    never serve a mismatched executable.
    """
    if model is not None and not isinstance(model, str):
        model = f"{type(model).__module__}.{type(model).__qualname__}"
    if mesh is not None and not isinstance(mesh, str):
        try:
            mesh = ",".join(
                f"{ax}={n}" for ax, n in
                zip(mesh.axis_names, mesh.devices.shape)
            )
        except Exception:
            mesh = repr(mesh)
    if layout is not None and not isinstance(layout, str):
        layout = getattr(layout, "name", None) or repr(layout)
    parts = {
        "model": model,
        "precision": str(precision) if precision is not None else None,
        "mesh": mesh,
        "layout": layout,
        "rules": [
            (r.pattern, list(r.spec)) if hasattr(r, "pattern") else repr(r)
            for r in rules
        ] if rules else None,
        "decode_plan": str(decode_plan) if decode_plan is not None else None,
        "buckets": list(buckets) if buckets is not None else None,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
    }
    blob = json.dumps(parts, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:32]


def _load_manifest(cache_dir: str) -> dict:
    try:
        with open(os.path.join(cache_dir, _MANIFEST)) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _save_manifest(cache_dir: str, manifest: dict) -> None:
    """Atomic write (tmp + rename) so concurrent children never see a torn
    manifest — the bench's cold and warm legs share one cache dir."""
    try:
        os.makedirs(cache_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(manifest, f, sort_keys=True)
        os.replace(tmp, os.path.join(cache_dir, _MANIFEST))
    except OSError as e:  # cache dir is best-effort, never fatal
        logger.warning("could not persist aot manifest: %s", e)


# -- abstract shape ladders ---------------------------------------------------

def _is_batch_array(key: str, value) -> bool:
    """The array fields a step consumes: leading-dim tensors plus the
    bucket-padding ``_mask``; every other underscore stamp is host-side."""
    if key == "_mask":
        return True
    return not key.startswith("_") and getattr(value, "ndim", 0) >= 1


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=getattr(x, "sharding", None),
        )
        if hasattr(x, "dtype")
        else x,
        tree,
    )


def batch_specs_for_ladder(
    example_batch: dict,
    buckets: tuple | list | None = None,
    data_axis: str = "data",
) -> list[dict]:
    """Every batch signature the driver can dispatch, as ShapeDtypeStructs.

    From a concrete example batch (full batch size ``B``): the full batch
    without ``_mask`` (the steady-state shape) plus each ``pad_to_bucket``
    ladder size *with* its f32 ``_mask`` — partial tails always carry the
    mask, full batches from normal assembly never do.

    A committed batch sharding over a MODEL axis (``fsdp`` without the
    data fold, ``tp`` anywhere) is rejected here, at build time:
    lowering the ladder against it would compile a wrong program and
    the error would otherwise surface deep inside jit at the first
    dispatch (:func:`blendjax.parallel.validate_batch_sharding`).
    """
    from blendjax.parallel.sharding import validate_batch_sharding

    fields = {
        k: v for k, v in example_batch.items()
        if k != "_mask" and _is_batch_array(k, v)
    }
    if not fields:
        raise ValueError("example batch has no array fields to lower against")
    for k, v in fields.items():
        sh = getattr(v, "sharding", None)
        if sh is not None:
            validate_batch_sharding(
                sh, data_axis=data_axis, what=f"ladder batch field {k!r}"
            )
    lead = next(iter(fields.values())).shape[0]
    ladder = tuple(buckets) if buckets else bucket_sizes(lead)
    specs = []

    def _field_sharding(v, shape):
        """Carry the example batch's committed sharding into the spec —
        a mesh run's live batches arrive sharded over the data axis,
        and an executable lowered against a replicated batch is a
        different program (no grad-sync collectives, rejected layouts
        at dispatch). Only reused when the bucketed lead still divides
        over it; numpy example batches have no sharding and lower
        exactly as before."""
        sharding = getattr(v, "sharding", None)
        if sharding is not None:
            try:
                sharding.shard_shape(tuple(shape))
            except Exception:
                sharding = None
        return sharding

    def _spec(size: int, with_mask: bool) -> dict:
        out = {}
        for k, v in fields.items():
            shape = (size,) + tuple(v.shape[1:])
            out[k] = jax.ShapeDtypeStruct(
                shape, np.dtype(v.dtype),
                sharding=_field_sharding(v, shape),
            )
        if with_mask:
            out["_mask"] = jax.ShapeDtypeStruct((size,), np.dtype(np.float32))
        return out

    specs.append(_spec(lead, with_mask=False))
    for size in ladder:
        specs.append(_spec(int(size), with_mask=True))
    return specs


def _signature(fields: dict) -> tuple:
    return tuple(
        sorted(
            (k, tuple(np.shape(v)), np.dtype(v.dtype).str)
            for k, v in fields.items()
        )
    )


# -- the AOT step set ---------------------------------------------------------

class AotStepSet:
    """Precompiled executables per batch signature, jit fallback elsewhere.

    ``jit(...).lower(...).compile()`` does **not** seed the jit wrapper's
    own dispatch cache, so holding the compiled executables and dispatching
    to them directly is what actually makes step 0 instant.  The wrapped
    jit takes shapes outside the ladder, each counted under
    ``train.aot_fallbacks``.
    """

    def __init__(self, step, compiled: dict, compile_ms: float,
                 cache_hits: int, cache_misses: int) -> None:
        self._step = step
        self._compiled = compiled
        self.compile_ms = compile_ms
        self.cache_hits = cache_hits
        self.cache_misses = cache_misses
        self.ledger_entries: list = []

    @property
    def signatures(self) -> tuple:
        return tuple(self._compiled)

    def __call__(self, state, batch):
        fields = {k: v for k, v in batch.items() if _is_batch_array(k, v)}
        exe = self._compiled.get(_signature(fields))
        if exe is not None:
            # an executable that rejects a batch of its own signature
            # (layout drift) is a fault: it raises, it is not rerouted
            return exe(state, fields)
        metrics.count("train.aot_fallbacks")
        return self._step(state, fields)


def build_aot_step(
    step,
    state,
    example_batch: dict,
    *,
    buckets: tuple | list | None = None,
    cache_dir: str | None = None,
    key: str | None = None,
    mesh=None,
    data_axis: str = "data",
    ledger_name: str = "aot_step",
) -> AotStepSet:
    """Compile ``step`` for every ladder signature before step 0.

    ``step`` must be a ``jax.jit`` wrapper (lowerable); ``state`` the
    concrete train state (its shapes/dtypes/shardings become the abstract
    state); ``example_batch`` a concrete full-size batch dict.  With
    ``cache_dir`` set, the persistent compilation cache is configured
    (:func:`configure_compilation_cache` — XLA's entries go where
    ``JAX_COMPILATION_CACHE_DIR`` says when that is set; the manifest
    always lives in ``cache_dir``) and
    the keyed manifest decides hit/miss per signature — a warm manifest
    entry means XLA will be served from disk, and ``train.aot_cache_hits``
    counts it; a cold one counts ``train.aot_cache_misses``.

    Every compiled executable is registered with the device ledger
    (cost/memory/collective accounting published as ``device.*`` gauges;
    ``mesh`` enables per-axis collective attribution) — the entries land
    on ``AotStepSet.ledger_entries`` so the drivers can derive the
    cost-model MFU numerator. Registration is accounting only and can
    never fail the build.
    """
    manifest: dict = {}
    seen: set = set()
    if cache_dir:
        configure_compilation_cache(cache_dir)
        manifest = _load_manifest(cache_dir)
        key = key or cache_key()
        seen = set(manifest.get(key, ()))

    state_spec = _abstract(state)
    specs = batch_specs_for_ladder(example_batch, buckets, data_axis=data_axis)
    compiled: dict = {}
    hits = misses = 0
    t0 = time.monotonic()
    with metrics.span("train.compile_ms"):
        for spec in specs:
            sig = _signature(spec)
            if sig in compiled:
                continue
            sig_hash = hashlib.sha256(repr(sig).encode()).hexdigest()[:16]
            if cache_dir:
                if sig_hash in seen:
                    hits += 1
                    metrics.count("train.aot_cache_hits")
                else:
                    misses += 1
                    metrics.count("train.aot_cache_misses")
                    seen.add(sig_hash)
            compiled[sig] = step.lower(state_spec, spec).compile()
    compile_ms = (time.monotonic() - t0) * 1e3
    if cache_dir:
        manifest[key] = sorted(seen)
        _save_manifest(cache_dir, manifest)
    logger.info(
        "aot step set: %d signatures compiled in %.0f ms (%d warm, %d cold)",
        len(compiled), compile_ms, hits, misses,
    )
    step_set = AotStepSet(step, compiled, compile_ms, hits, misses)
    try:
        step_set.ledger_entries = ledger.register_aot_set(
            ledger_name, compiled, mesh=mesh
        )
    except Exception:  # pragma: no cover - accounting must not fail builds
        logger.debug("device ledger registration failed", exc_info=True)
    return step_set
