"""MeshTrainDriver: the live pipeline as one data-parallel program on a
named mesh.

``dryrun_multichip`` has long validated dp/fsdp/tp meshes to f32-exact
equivalence on 8 devices, but the *live* path — ShardedHostIngest ->
DeviceFeeder -> TrainDriver -> echo reservoir — ran on exactly one chip.
This module promotes the dryrun into the first-class driver (ROADMAP
item 1: "the structural refactor that makes every other item scale"):

- the :class:`~blendjax.data.pipeline.StreamDataPipeline` takes
  ``mesh=`` and places every ingest batch as a global ``jax.Array``
  sharded over ``data`` (one grouped placement per batch single-host,
  one ``make_array_from_process_local_data`` per field multihost — no
  per-device host loops, bjx-lint BJX111);
- :func:`make_mesh_supervised_step` / :func:`make_mesh_fused_step`
  build the SAME jitted steps the single-chip path runs, with explicit
  ``in_shardings``/``out_shardings`` pinned from the concrete train
  state — donation requires matching in/out layouts, and pinning them
  means a jit upgrade or a stray resharded input can never silently
  move the optimizer state mid-run;
- :class:`MeshTrainDriver` keeps the completion-tracked dispatch ring,
  device-timeline metrics, and live MFU gauge working unchanged on
  sharded outputs: the readiness poll (``transfer_done``) reads the
  GLOBAL array's ready bit, and MFU scales ``peak_flops_per_chip`` by
  the participating chip count;
- the :class:`~blendjax.data.echo.SampleReservoir` ring shards over
  ``data`` too (``EchoingPipeline(mesh=...)``), so echo capacity grows
  with the mesh and drawn batches leave pre-sharded in the feeder's
  batch layout.

Training semantics are layout-free: the same recorded stream through a
1-device and an 8-device mesh produces f32-identical losses
(tests/test_mesh_driver.py pins it), and throughput scales with chips —
the ``multichip_live`` bench row measures img/s at mesh sizes 1/2/4/8
with a scaling-efficiency figure.
"""

from __future__ import annotations

# bjx: driver-hot-path (BJX106/BJX108 hold here exactly as in driver.py)
# bjx: mesh-hot-path (BJX111: no per-device placement loops, no host
# materialization of global arrays in the dispatch loop)

from blendjax.train.driver import TrainDriver


def _require_jax():
    import jax

    return jax


def _state_jit_shardings(state, mesh):
    """The sharding pytree pinning a concrete state's layout through a
    ``step(state, *rest) -> (state, metrics)`` jit — the public helper
    normalized onto the driver's mesh (see
    :func:`blendjax.parallel.state_shardings` for the rules)."""
    from blendjax.parallel.sharding import state_shardings

    return state_shardings(state, mesh=mesh)


def make_mesh_supervised_step(
    state,
    mesh,
    loss_fn=None,
    donate: bool = True,
    augment=None,
    augment_rng=None,
    data_axis: str = "data",
):
    """:func:`blendjax.train.make_supervised_step` with the layout made
    explicit: ``in_shardings``/``out_shardings`` are pinned from the
    concrete ``state`` (params/optimizer leaves keep the mesh rules
    they were created with), so the donated update reuses the sharded
    buffers in place and can never drift layouts across a run. The
    batch side stays unspecified — it arrives committed to the batch
    sharding by the feeder (or the echo reservoir), and jit infers it.

    ONE step body: this delegates to the plain builder with the state
    sharding threaded through, so single-chip and mesh runs can never
    train different math.
    """
    from blendjax.train.steps import loss_on_mesh, make_supervised_step

    return make_supervised_step(
        loss_fn=loss_on_mesh(loss_fn, mesh, data_axis),
        donate=donate, augment=augment,
        augment_rng=augment_rng,
        state_sharding=_state_jit_shardings(state, mesh),
    )


def make_mesh_fused_step(
    state,
    mesh,
    loss_fn=None,
    donate: bool = True,
    augment=None,
    augment_rng=None,
    data_axis: str = "data",
):
    """:func:`blendjax.train.make_fused_tile_step` with pinned state
    shardings: the still-encoded packed group decodes INSIDE the train
    jit (one device dispatch per step, zero standalone decode calls —
    the invariants the single-chip driver established) while the state
    layout is held by explicit ``in_shardings``/``out_shardings``.

    ONE step body: this delegates to the plain builder, adding only
    the mesh-specific pieces — the pinned state sharding tree, and an
    in-jit constraint that re-shards the just-decoded (K, B, ...)
    fields onto the batch axis (the packed wire buffer arrives
    replicated because bytes can't shard, and without the constraint
    GSPMD is free to keep the whole scan replicated per chip — data
    parallelism in name only)."""
    jax = _require_jax()

    from blendjax.train.steps import make_fused_tile_step

    if data_axis not in mesh.axis_names:
        # fail at build time: a typo'd/missing batch axis would
        # otherwise silently constrain the scan to REPLICATED — 1x
        # throughput at N chips, no error
        raise ValueError(
            f"data_axis {data_axis!r} is not an axis of mesh "
            f"{dict(mesh.shape)}"
        )

    def _pin_batch_axis(superbatch):
        from jax.sharding import NamedSharding, PartitionSpec

        from blendjax.parallel.sharding import batch_sharding

        bs = batch_sharding(mesh, axis=data_axis)
        sb = NamedSharding(mesh, PartitionSpec(None, *(bs.spec or ())))
        return {
            k: (
                jax.lax.with_sharding_constraint(v, sb)
                if getattr(v, "ndim", 0) >= 2 else v
            )
            for k, v in superbatch.items()
        }

    return make_fused_tile_step(
        loss_fn=loss_fn, donate=donate, augment=augment,
        augment_rng=augment_rng,
        state_sharding=_state_jit_shardings(state, mesh),
        superbatch_constraint=_pin_batch_axis,
        mesh=mesh, data_axis=data_axis,
    )


def make_mesh_echo_fused_step(
    state,
    mesh,
    reservoir,
    loss_fn=None,
    donate: bool = True,
    precision=None,
    data_axis: str = "data",
):
    """:func:`blendjax.train.make_echo_fused_step` with the mesh
    layouts made explicit: the state's ``in_shardings``/
    ``out_shardings`` pinned from the concrete ``state`` (the donated
    update can never drift layouts), the reservoir RING's
    ``data``-axis sharding pinned into the jit's buffer argument (a
    drifted ring placement fails loudly at dispatch instead of
    silently resharding the multi-GB ring every step), and an in-jit
    constraint re-sharding the just-gathered batch over the batch
    axis — the same hook trio ``make_mesh_fused_step`` uses for
    packed groups.

    ``reservoir`` is the :class:`blendjax.data.echo.SampleReservoir`
    backing the ``EchoingPipeline(mesh=..., emit_draws=True)`` this
    step trains from; its ring sharding must cover ``data_axis``
    (construct the pipeline with ``mesh=``). ONE step body: delegates
    to the plain builder, so single-chip and mesh echo runs train
    identical math."""
    jax = _require_jax()

    from blendjax.train.steps import loss_on_mesh, make_echo_fused_step

    if data_axis not in mesh.axis_names:
        # same build-time failure as make_mesh_fused_step: a typo'd
        # batch axis would silently train replicated
        raise ValueError(
            f"data_axis {data_axis!r} is not an axis of mesh "
            f"{dict(mesh.shape)}"
        )
    ring_sharding = getattr(reservoir, "sharding", None)
    if ring_sharding is None:
        raise ValueError(
            "the reservoir ring is not mesh-sharded — construct the "
            "EchoingPipeline (or SampleReservoir) with mesh=/sharding= "
            "so echo capacity shards over the data axis"
        )

    def _pin_drawn_batch(batch):
        from blendjax.parallel.sharding import batch_sharding

        bs = batch_sharding(mesh, axis=data_axis)
        return {
            k: (
                jax.lax.with_sharding_constraint(v, bs)
                if getattr(v, "ndim", 0) >= 1 else v
            )
            for k, v in batch.items()
        }

    return make_echo_fused_step(
        reservoir_draw=reservoir.draw,
        loss_fn=loss_on_mesh(loss_fn, mesh, data_axis),
        donate=donate, precision=precision,
        state_sharding=_state_jit_shardings(state, mesh),
        buffer_sharding=ring_sharding,
        draw_constraint=_pin_drawn_batch,
    )


class MeshTrainDriver(TrainDriver):
    """:class:`~blendjax.train.driver.TrainDriver` running the live
    loop on a named mesh.

    Everything the single-chip driver proved carries over unchanged —
    the completion-tracked dispatch ring polls readiness on the GLOBAL
    array (one bit covering every shard), device-timeline histograms
    time dispatch->retirement of the sharded program, and exactly one
    device dispatch per step — while throughput and MFU account for
    the whole mesh:

    - ``peak_flops_per_chip`` (or a pre-scaled ``peak_flops``) is
      multiplied by the participating chip count — ALL processes'
      chips, since the jitted step is one SPMD program over the global
      batch — so the live ``train.mfu`` gauge reads the same whether
      one chip or 64 run the step;
    - ``stats`` carries ``chips``/``processes`` beside the ring
      numbers, and per-chip throughput is ``images/s / chips``;
    - :meth:`fleet_snapshots`/:meth:`fleet_report` aggregate each
      process's doctor/lineage/trace view into one fleet report
      (:mod:`blendjax.obs.fleetview`), process index tagged.

    Build the step with :func:`make_mesh_supervised_step` (decoded
    batches, echo path) or :func:`make_mesh_fused_step` (packed tile/pal
    groups), pair with ``StreamDataPipeline(mesh=mesh, ...)``, and the
    entire ingest->train loop is mesh-resident.
    """

    def __init__(self, step, state, mesh, *, data_axis: str = "data",
                 inflight: int = 4, sync_every: int = 32,
                 pad_partial: bool = True, buckets=None,
                 flops_per_image: float | None = None,
                 peak_flops_per_chip: float | None = None,
                 peak_flops: float | None = None,
                 checkpoint=None, checkpoint_every: int = 0,
                 session_state=None, place=None):
        from blendjax.parallel.sharding import mesh_chip_count

        self.mesh = mesh
        self.data_axis = data_axis
        self.chips = mesh_chip_count(mesh)
        if peak_flops is None and peak_flops_per_chip:
            peak_flops = float(peak_flops_per_chip) * self.chips
        super().__init__(
            step, state, inflight=inflight, sync_every=sync_every,
            pad_partial=pad_partial, buckets=buckets,
            flops_per_image=flops_per_image, peak_flops=peak_flops,
            checkpoint=checkpoint, checkpoint_every=checkpoint_every,
            session_state=session_state, place=place,
        )

    @classmethod
    def build(cls, model, mesh=None, example_batch=None, loss_fn=None,
              fused: bool = False, optimizer=None,
              learning_rate: float = 1e-3, rng=None, augment=None,
              augment_rng=None, aot: bool = False,
              aot_cache_dir: str | None = None, aot_batch=None,
              layout=None, rules=None,
              **driver_kwargs):
        """One call from model to mesh-resident driver: init the train
        state sharded by the layout's partition rules (params over
        ``fsdp``/``tp`` where the axes exist, replicated otherwise —
        see ``param_sharding_rules``/``resolve_rules``), build the
        pinned-sharding step (``fused=True`` for packed tile/pal
        streams), and wrap the driver. ``example_batch`` is one host
        batch of the stream's image field (shapes only; values never
        train).

        ``layout`` (a :class:`blendjax.parallel.Layout`, a name like
        ``"data×fsdp"``/``"data2xfsdp4"``, or an axis dict) selects
        the mesh composition AND the partition rules in one spelling;
        with ``mesh=None`` the mesh is created from it. ``rules``
        overrides the rule set explicitly (a tuple of
        :class:`~blendjax.parallel.PartitionRule`); without either the
        model's own ``partition_rules()`` applies when it defines one.

        ``aot=True`` with ``aot_batch`` (a full example batch dict —
        image + the loss's fields) AOT-compiles the step for every
        bucket-ladder shape before step 0, behind the persistent
        compilation cache when ``aot_cache_dir`` is set (docs/
        performance.md "Instant start"). The fused tile step is a host
        dispatcher over inner jits and is not lowerable as one unit, so
        AOT applies to the supervised step only."""
        import time as _time

        from blendjax.parallel.sharding import (
            resolve_layout,
            resolve_rules,
            validate_batch_sharding,
        )
        from blendjax.train.steps import make_train_state

        t0 = _time.monotonic()
        data_axis = driver_kwargs.get("data_axis", "data")
        if mesh is None:
            if layout is None:
                raise ValueError(
                    "MeshTrainDriver.build needs a mesh or a layout — "
                    "pass mesh=create_mesh(...) or layout='data×fsdp'"
                )
            mesh = resolve_layout(layout).create_mesh()
        if example_batch is None:
            raise ValueError("example_batch is required (shapes only)")
        rules = resolve_rules(rules=rules, layout=layout, model=model)
        if aot_batch is not None:
            # build-time gate: a model-axis-sharded *batch* compiles a
            # wrong program (satellite of the layout system; see
            # validate_batch_sharding)
            for k, v in aot_batch.items():
                sh = getattr(v, "sharding", None)
                if sh is not None:
                    validate_batch_sharding(
                        sh, data_axis=data_axis, what=f"aot_batch[{k!r}]"
                    )
        state = make_train_state(
            model, example_batch, optimizer=optimizer,
            learning_rate=learning_rate, rng=rng, mesh=mesh,
            rules=rules,
        )
        if fused:
            step = make_mesh_fused_step(
                state, mesh, loss_fn=loss_fn, augment=augment,
                augment_rng=augment_rng,
                # the fused step re-shards decoded fields over the SAME
                # axis the driver/pipeline use
                data_axis=driver_kwargs.get("data_axis", "data"),
            )
        else:
            step = make_mesh_supervised_step(
                state, mesh, loss_fn=loss_fn, augment=augment,
                augment_rng=augment_rng,
                data_axis=driver_kwargs.get("data_axis", "data"),
            )
        ledger_entry = None
        if aot and not fused and aot_batch is not None:
            from blendjax.train.aot import build_aot_step, cache_key

            buckets = driver_kwargs.get("buckets")
            step = build_aot_step(
                step, state, aot_batch, buckets=buckets,
                cache_dir=aot_cache_dir,
                key=cache_key(
                    model=model, mesh=mesh, buckets=buckets,
                    layout=layout, rules=rules,
                ) if aot_cache_dir else None,
                mesh=mesh, data_axis=data_axis,
                ledger_name=f"{type(model).__name__}.mesh_supervised_step",
            )
        elif aot_batch is not None and not fused:
            # Accounting-only registration for the non-AOT path: one
            # extra lower+compile (served from the persistent cache on
            # the first real dispatch) buys the mesh's per-collective
            # byte breakdown + cost-model FLOPs at build time. Opt-in
            # by passing aot_batch; guarded inside register_step.
            from blendjax.obs.devledger import ledger

            ledger_entry = ledger.register_step(
                f"{type(model).__name__}.mesh_supervised_step",
                step, state, aot_batch, mesh=mesh,
            )
        drv = cls(step, state, mesh, **driver_kwargs)
        # the committed layout, by name — bench rows and fleet reports
        # tag throughput/collective figures with it
        drv.layout = (
            resolve_layout(layout).name if layout is not None
            else "×".join(mesh.axis_names)
        )
        drv._adopt_cost_model_flops(
            step, {"image": example_batch},
            entries=[ledger_entry] if ledger_entry else None,
        )
        drv._t_created = t0
        drv.startup_ms = (_time.monotonic() - t0) * 1e3
        return drv

    def batch_sharding(self):
        """The layout live batches must arrive in (what
        ``StreamDataPipeline(mesh=...)`` produces)."""
        from blendjax.parallel.sharding import batch_sharding

        return batch_sharding(self.mesh, axis=self.data_axis)

    # -- fleet observability --------------------------------------------------

    def fleet_snapshots(self, prefetch: int | None = None) -> list:
        """Every participating process's observability snapshot
        (metrics/lineage/trace/doctor verdict), process-index tagged;
        single-process runs return just the local one."""
        from blendjax.obs.fleetview import gather_fleet_snapshots

        return gather_fleet_snapshots(driver=self.stats, prefetch=prefetch)

    def fleet_report(self, prefetch: int | None = None) -> dict:
        """One aggregated fleet view over :meth:`fleet_snapshots`
        (:func:`blendjax.obs.fleetview.fleet_report`)."""
        from blendjax.obs.fleetview import fleet_report

        return fleet_report(self.fleet_snapshots(prefetch=prefetch))

    @property
    def stats(self) -> dict:
        s = TrainDriver.stats.fget(self)
        s["chips"] = self.chips
        if getattr(self, "layout", None):
            s["layout"] = self.layout
        try:
            s["processes"] = _require_jax().process_count()
        except Exception:
            s["processes"] = 1
        return s


__all__ = [
    "MeshTrainDriver",
    "make_mesh_echo_fused_step",
    "make_mesh_fused_step",
    "make_mesh_supervised_step",
]
