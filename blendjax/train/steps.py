"""Jitted training steps over mesh-sharded streamed batches.

These builders leave layouts to propagate from the arrays (jit infers;
GSPMD partitions). For the multi-chip LIVE loop use their
pinned-sharding twins in :mod:`blendjax.train.mesh_driver`
(``make_mesh_supervised_step`` / ``make_mesh_fused_step``): identical
training math, with ``in_shardings``/``out_shardings`` pinned from the
concrete state so the donated update can never drift layouts mid-run.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training.train_state import TrainState

from blendjax.parallel.sharding import param_sharding_rules
from blendjax.train.precision import policy_value_and_grad, resolve_policy
from blendjax.utils.metrics import (
    COUNTERS_COLLECTION,
    SCOPE_DECODE,
    SCOPE_OPTIMIZER,
    SCOPE_RESHARD,
)


def make_train_state(
    model,
    example_input,
    optimizer=None,
    learning_rate: float = 1e-3,
    rng=None,
    mesh=None,
    rules=None,
    layout=None,
) -> TrainState:
    """Init params (sharded onto ``mesh`` per the partition rules) and
    wrap them with an optax optimizer in a flax TrainState.

    ``rules``/``layout`` select the parameter layout
    (:func:`blendjax.parallel.resolve_rules`: explicit rules win, then
    the layout's, then the model's own ``partition_rules()``, then the
    generic fsdp/tp defaults). Optimizer moments inherit the params'
    shardings through ``optax``'s ``zeros_like`` init, so one
    device_put here commits the WHOLE state to the layout."""
    from blendjax.parallel.sharding import resolve_rules

    rng = rng if rng is not None else jax.random.key(0)
    optimizer = optimizer or optax.adamw(learning_rate)
    params = model.init(rng, example_input)["params"]
    if mesh is not None:
        resolved = resolve_rules(rules=rules, layout=layout, model=model)
        params = jax.tree_util.tree_map_with_path(
            lambda p, v: jax.device_put(
                v, param_sharding_rules(mesh, p, v, rules=resolved)
            ),
            params,
        )
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=optimizer
    )
    if mesh is not None:
        # moments inherit the params' shardings via optax zeros_like,
        # but optimizer scalars created fresh (adam's count) land on
        # the default device — commit them replicated so the WHOLE
        # state lives on the mesh and pinned jit shardings stay
        # mesh-uniform
        rep = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()
        )
        state = jax.tree_util.tree_map(
            lambda v: (
                v
                if not hasattr(v, "sharding")
                or isinstance(v.sharding, jax.sharding.NamedSharding)
                else jax.device_put(v, rep)
            ),
            state,
        )
    return state


def corner_loss(pred, xy, image_shape=None, mask=None):
    """MSE over predicted corner pixels, normalized to [0,1] image coords
    so the loss is resolution-independent.

    ``mask`` (lead,) marks valid rows of a bucket-padded partial batch
    (``blendjax.data.batcher.pad_to_bucket``): padded rows contribute
    nothing and the mean divides by the true row count, so a padded
    batch scores — and backpropagates — identically to its exact-shape
    form (up to float associativity). ``mask=None`` is bit-for-bit the
    old unmasked loss."""
    if image_shape is not None:
        h, w = image_shape
        scale = jnp.asarray([w, h], jnp.float32)
        pred = pred / scale
        xy = xy / scale
    err = (pred - xy.astype(jnp.float32)) ** 2
    if mask is None:
        return jnp.mean(err)
    per = err.reshape(err.shape[0], -1).mean(axis=1)
    m = mask.astype(jnp.float32)
    return (per * m).sum() / jnp.maximum(m.sum(), 1.0)


def _default_loss(state, params, batch):
    """ONE default loss for all step builders (per-batch, chunked, and
    fused runs must score identically): corner regression with the
    bucket-padding ``_mask`` honored when present, so mask-padded tail
    batches train without recompiles or loss skew."""
    return corner_loss(
        state.apply_fn({"params": params}, batch["image"]),
        batch["xy"],
        image_shape=batch["image"].shape[1:3],
        mask=batch.get("_mask"),
    )


def loss_on_mesh(loss_fn, mesh, data_axis: str = "data"):
    """``loss_fn`` (default: the builders' own) traced with the
    program's mesh declared to the ops below the model: a Pallas kernel
    reached through the model (the fused attention core) cannot be
    partitioned by GSPMD and wraps itself in ``shard_map`` over
    ``data_axis`` instead. ``mesh=None`` returns ``loss_fn`` as given."""
    loss_fn = loss_fn or _default_loss
    if mesh is None:
        return loss_fn

    from blendjax.ops.attention import batch_sharded_over

    def on_mesh(state, params, batch):
        with batch_sharded_over(mesh, data_axis):
            return loss_fn(state, params, batch)

    return on_mesh


def counting(loss_fn):
    """``loss_fn(state, params, batch) -> loss`` as ``-> (loss,
    counters)``: the model is applied with its ``counters`` collection
    mutable, and what its layers sow there (integers, e.g.
    :class:`blendjax.models.moe.RoutedExperts`'s pick counts) comes out
    summed by name over the layers, ``{name: int32 scalar}``. Where
    nothing is sown the dict is empty and the program is the one
    ``loss_fn`` alone traces. An ``apply_fn`` that is not a flax
    module's is left as it is."""

    def counted(state, params, batch):
        apply = getattr(state, "apply_fn", None)
        if not isinstance(getattr(apply, "__self__", None), nn.Module):
            return loss_fn(state, params, batch), {}
        sown = []

        def apply_fn(variables, *args, **kwargs):
            out, cols = apply(
                variables, *args, mutable=[COUNTERS_COLLECTION], **kwargs
            )
            sown.append(cols.get(COUNTERS_COLLECTION, {}))
            return out

        loss = loss_fn(state.replace(apply_fn=apply_fn), params, batch)
        totals: dict = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
            name = next(
                k.key for k in reversed(path)
                if isinstance(k, jax.tree_util.DictKey)
            )
            totals[name] = totals.get(name, 0) + leaf
        return loss, totals

    return counted


def _step_metrics(loss, counters) -> dict:
    """A step's metrics: ``{"loss": loss}``, and where the model counted
    anything, ``"counters"`` summed over the step's updates (the leading
    axis of a scan's or microbatches' stacked counts)."""
    if not counters:
        return {"loss": loss}
    return {
        "loss": loss,
        "counters": {k: jnp.sum(v) for k, v in counters.items()},
    }


def _sharding_jit_kwargs(state_sharding, n_data_args: int = 1,
                         data_shardings: dict | None = None) -> dict:
    """jit kwargs pinning a state's layout: ``in_shardings``/
    ``out_shardings`` with the state tree explicit and every data arg
    (and the metrics output) left unspecified for jit to infer. The
    mesh builders (:mod:`blendjax.train.mesh_driver`) pass the
    concrete state's sharding tree here; ``None`` for both keeps the
    plain propagate-from-arrays jit. ``data_shardings`` pins specific
    data args too (``{arg_index: sharding}``, 0 = the state): the echo
    path pins the reservoir ring's ``data``-axis layout so a drifted
    buffer placement fails loudly at dispatch instead of silently
    resharding the (potentially multi-GB) ring every step — honored
    with or without a state pin (a buffer-only caller must not lose
    the guarantee silently)."""
    if state_sharding is None and not data_shardings:
        return {}
    in_sh = [state_sharding] + [None] * n_data_args
    for i, sh in (data_shardings or {}).items():
        in_sh[i] = sh
    out: dict = {"in_shardings": tuple(in_sh)}
    if state_sharding is not None:
        out["out_shardings"] = (state_sharding, None)
    return out


def make_supervised_step(
    mesh=None,
    batch_sharding=None,
    loss_fn=None,
    donate: bool = True,
    accum_steps: int = 1,
    augment=None,
    augment_rng=None,
    state_sharding=None,
    precision=None,
):
    """Build ``step(state, batch) -> (state, metrics)``.

    - ``batch`` is the dict the ingest pipeline yields (tensor fields
      only); the uint8->compute-dtype cast happens inside the jitted step.
    - sharding is carried by the arrays themselves: the feeder places the
      batch under ``batch_sharding`` and params under the mesh rules; jit
      infers and GSPMD propagates, so no explicit in_shardings needed.
    - donation reuses the state's device buffers step-over-step.
    - ``accum_steps=N`` splits the batch's leading axis into N
      microbatches and accumulates gradients over a ``lax.scan`` before
      the single optimizer update — activation memory scales with the
      microbatch while the optimizer sees the full batch (gradients are
      identical to the unaccumulated step up to float associativity).
    - ``augment`` is an optional ``fn(rng, images) -> images``
      (:mod:`blendjax.ops.augment`) applied to ``batch['image']`` INSIDE
      the jitted step — on device, sharded with the batch, fused into
      the input cast. The per-step key folds ``augment_rng`` (default
      key 0) with the training step counter, so runs are deterministic
      and checkpoint-resume replays the same augmentation sequence.
      ONLY ``batch['image']`` is transformed: with spatial labels
      (pixel coordinates, masks), geometric ops like flip/crop would
      desynchronize image and label — use photometric ops there, or
      apply a paired transform in ``loss_fn`` instead.
    - ``state_sharding`` (a pytree of shardings matching the concrete
      train state) pins the jit's ``in_shardings``/``out_shardings``
      for the state argument — the mesh path's layout-stability
      guarantee (``blendjax.train.mesh_driver`` supplies it; plain
      single-chip callers leave it ``None``).
    - ``precision`` names a :mod:`blendjax.train.precision` policy (or
      passes one). ``None``/``"bf16-compute"`` keeps today's numerics;
      ``"bf16-grads"`` differentiates w.r.t. the bf16-cast params so
      gradients — and the cross-chip gradient all-reduce of a
      ``data``-sharded batch — cross the mesh in bf16 (half the
      bytes), cast back to f32 before the optimizer.
    """
    # layouts ride on the arrays (see above); the mesh is only declared
    # to the kernels below the model
    data_axis = (getattr(batch_sharding, "spec", None) or ("data",))[0]
    loss_fn = counting(loss_on_mesh(loss_fn, mesh, data_axis))
    base_rng = _resolve_augment_rng(augment, augment_rng)
    policy = resolve_policy(precision)
    accum_steps = max(1, int(accum_steps))

    def step(state, batch):
        if augment is not None:
            rng = jax.random.fold_in(base_rng, state.step)
            batch = {**batch, "image": augment(rng, batch["image"])}

        def scalar_loss(params, b):
            return loss_fn(state, params, b)

        if accum_steps == 1:
            (loss, counters), grads = policy_value_and_grad(
                lambda p: scalar_loss(p, batch), state.params, policy,
                has_aux=True,
            )
        else:
            # Split only the real batch tensors; scalar sidecar fields
            # the pipeline attaches (producer btid stamps, '_meta', ...)
            # ride alongside every microbatch unchanged.
            lead = next(
                (
                    v.shape[0]
                    for v in batch.values()
                    if hasattr(v, "ndim") and getattr(v, "ndim", 0) >= 1
                ),
                0,
            )
            if lead % accum_steps:
                raise ValueError(
                    f"batch leading dim {lead} not divisible by "
                    f"accum_steps={accum_steps}"
                )

            def splittable(v):
                return (
                    hasattr(v, "ndim")
                    and getattr(v, "ndim", 0) >= 1
                    and v.shape[0] == lead
                )

            micro = {
                k: v.reshape(accum_steps, lead // accum_steps, *v.shape[1:])
                for k, v in batch.items()
                if splittable(v)
            }
            side = {k: v for k, v in batch.items() if k not in micro}

            def body(carry, mb):
                loss_sum, grad_sum = carry
                # policy_value_and_grad hands back grads already cast
                # to the master params' dtype (f32), so the zeros_like
                # accumulator below IS the policy's f32 accum_dtype
                (loss, counters), grads = policy_value_and_grad(
                    lambda p: scalar_loss(p, {**side, **mb}),
                    state.params, policy, has_aux=True,
                )
                return (
                    loss_sum + loss,
                    jax.tree.map(jnp.add, grad_sum, grads),
                ), counters

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (loss_sum, grad_sum), counters = jax.lax.scan(
                body, (jnp.zeros(()), zeros), micro
            )
            loss = loss_sum / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grad_sum)
        with jax.named_scope(SCOPE_OPTIMIZER):
            state = state.apply_gradients(grads=grads)
        return state, _step_metrics(loss, counters)

    return jax.jit(
        step,
        donate_argnums=(0,) if donate else (),
        **_sharding_jit_kwargs(state_sharding),
    )


def _resolve_augment_rng(augment, augment_rng):
    """ONE default-key rule for all three step builders: per-batch,
    chunked, and fused runs must resolve the same base key or their
    augmentation sequences silently diverge."""
    if augment is None:
        return None
    return augment_rng if augment_rng is not None else jax.random.key(0)


def _chunk_scan_body(loss_fn, augment, base_rng, policy=None):
    """Shared scan body for the chunked/fused steps: one optimizer
    update per slice, with the optional augment keyed by ``st.step`` —
    the SAME fold the per-batch step uses (``make_supervised_step``),
    so K scanned updates replay the exact augmentation sequence K
    sequential per-batch calls would. ``policy`` routes the grad
    computation through :func:`policy_value_and_grad` (same rule as
    the per-batch step: chunked runs must not train different math).
    An update's output is ``(loss, counters)`` (:func:`counting`)."""
    policy = resolve_policy(policy)
    loss_fn = counting(loss_fn)

    def body(st, batch):
        if augment is not None:
            rng = jax.random.fold_in(base_rng, st.step)
            batch = {**batch, "image": augment(rng, batch["image"])}

        def scalar_loss(params):
            return loss_fn(st, params, batch)

        out, grads = policy_value_and_grad(
            scalar_loss, st.params, policy, has_aux=True
        )
        with jax.named_scope(SCOPE_OPTIMIZER):
            return st.apply_gradients(grads=grads), out

    return body


def make_chunked_supervised_step(
    loss_fn=None,
    donate: bool = True,
    augment=None,
    augment_rng=None,
    state_sharding=None,
    precision=None,
):
    """Build ``step(state, superbatch) -> (state, metrics)`` where
    ``superbatch`` fields carry a leading chunk axis: (K, B, ...).

    Runs K sequential optimizer updates (bit-identical training
    semantics to K calls of the per-batch step) inside ONE jitted
    ``lax.scan`` — one device round trip per K batches instead of per
    batch, which is the difference between working and crawling on
    high-latency device links (see docs/performance.md). Pairs with
    ``StreamDataPipeline(chunk=K)``. ``metrics['loss']`` is the K-vector
    of per-update losses; ``metrics['counters']``, present where the
    model sows counts (:func:`counting`), their sums over the K updates.

    ``augment``/``augment_rng`` mirror :func:`make_supervised_step`:
    the per-update key folds ``augment_rng`` with the state's step
    counter INSIDE the scan, so a chunked run augments identically to
    the same stream trained one batch at a time (and to a
    checkpoint-resumed run).
    """
    loss_fn = loss_fn or _default_loss
    base_rng = _resolve_augment_rng(augment, augment_rng)

    def step(state, superbatch):
        state, (losses, counters) = jax.lax.scan(
            _chunk_scan_body(loss_fn, augment, base_rng, precision),
            state, superbatch,
        )
        return state, _step_metrics(losses, counters)

    return jax.jit(
        step,
        donate_argnums=(0,) if donate else (),
        **_sharding_jit_kwargs(state_sharding),
    )


def make_fused_tile_step(
    loss_fn=None,
    donate: bool = True,
    augment=None,
    augment_rng=None,
    state_sharding=None,
    superbatch_constraint=None,
    precision=None,
    mesh=None,
    data_axis: str = "data",
):
    """Build ``step(state, packed_batch) -> (state, metrics)`` where
    ``packed_batch`` is what ``StreamDataPipeline(emit_packed=True)``
    yields: the still-encoded chunk group plus its decode plan — a tile
    group (``_refs``/``_names``/``_geoms``) or a full-frame palette
    group (``_pal``).

    Fuses the on-device reconstruction INTO the train jit: one device
    call per K batches where the decode-then-step pipeline costs two,
    and ZERO standalone ``decode.dispatch`` calls — decoded frames live
    only as fused-step intermediates, never round-tripping as
    standalone ``jax.Array``s. Training semantics are bit-identical to
    ``make_chunked_supervised_step`` over the decoded fields.

    A batch without ``"_packed"`` (the mixed-stream K'=1 degradation
    path, including mask-padded partial tails) falls back to the
    scan-only chunked step on its decoded fields — still one device
    call. Pairs with :class:`blendjax.train.TrainDriver` to keep
    several of these single-dispatch steps in flight.

    ``state_sharding`` pins the jits' in/out state layout (see
    :func:`make_supervised_step`); ``superbatch_constraint`` is an
    optional in-jit hook applied to the just-decoded superbatch before
    the scan — the mesh path re-shards the decoded fields over the
    batch axis there (``blendjax.train.mesh_driver``). Both default
    off with zero behavior change. ``mesh``/``data_axis`` go to the
    tile decode (:func:`blendjax.ops.tiles.decode_tile_delta`) and,
    through :func:`loss_on_mesh`, to the attention core: both need them
    to run their kernels inside a multi-device program.
    """
    loss_fn = loss_on_mesh(loss_fn, mesh, data_axis)
    chunked = make_chunked_supervised_step(
        loss_fn=loss_fn, donate=donate,
        augment=augment, augment_rng=augment_rng,
        state_sharding=state_sharding, precision=precision,
    )
    base_rng = _resolve_augment_rng(augment, augment_rng)
    pin = superbatch_constraint or (lambda sb: sb)

    def _fused(state, packed, refs, spec, names, geoms, rle):
        from blendjax.ops.tiles import decode_packed_superbatch

        with jax.named_scope(SCOPE_DECODE):
            superbatch = decode_packed_superbatch(
                packed, refs, spec, names, geoms, rle_groups=rle,
                mesh=mesh, data_axis=data_axis,
            )
        with jax.named_scope(SCOPE_RESHARD):
            superbatch = pin(superbatch)
        state, (losses, counters) = jax.lax.scan(
            _chunk_scan_body(loss_fn, augment, base_rng, precision), state,
            superbatch,
        )
        return state, _step_metrics(losses, counters)

    fused = jax.jit(
        _fused,
        static_argnames=("spec", "names", "geoms", "rle"),
        donate_argnums=(0,) if donate else (),
        **_sharding_jit_kwargs(state_sharding, n_data_args=2),
    )

    def _fused_pal(state, packed, spec, pal_groups, rle):
        from blendjax.ops.tiles import decode_packed_pal_superbatch

        with jax.named_scope(SCOPE_DECODE):
            superbatch = decode_packed_pal_superbatch(
                packed, spec, pal_groups, rle
            )
        with jax.named_scope(SCOPE_RESHARD):
            superbatch = pin(superbatch)
        state, (losses, counters) = jax.lax.scan(
            _chunk_scan_body(loss_fn, augment, base_rng, precision), state,
            superbatch,
        )
        return state, _step_metrics(losses, counters)

    fused_pal = jax.jit(
        _fused_pal,
        static_argnames=("spec", "pal_groups", "rle"),
        donate_argnums=(0,) if donate else (),
        **_sharding_jit_kwargs(state_sharding),
    )

    def step(state, batch):
        # static decode-plan args go POSITIONALLY: jit rejects keyword
        # arguments once in_shardings is pinned (the mesh path), and
        # the plain path resolves them identically either way. `_rle`
        # is the deferred run-length expansion plan ("ndr" wire frames
        # decompressed INSIDE this dispatch — docs/wire-protocol.md).
        if "_pal" in batch:
            return fused_pal(
                state, batch["_packed"], batch["_spec"], batch["_pal"],
                batch.get("_rle", ()),
            )
        if "_packed" in batch:
            return fused(
                state, batch["_packed"], batch["_refs"],
                batch["_spec"], batch["_names"], batch["_geoms"],
                batch.get("_rle", ()),
            )
        fields = {
            k: v for k, v in batch.items()
            if k != "_meta" and getattr(v, "ndim", 0) >= 1
        }
        return chunked(state, fields)

    # The dispatcher is not one lowerable program; its three jits are
    # reachable so the decode kernel can be looked for in what was
    # lowered, and their summed dispatch-cache size lets the driver's
    # RetraceAudit count a compile past warm-up on this path too.
    step.jits = {"tile": fused, "pal": fused_pal, "chunked": chunked}
    step._cache_size = lambda: sum(
        j._cache_size() for j in step.jits.values()
    )
    return step


def make_echo_fused_step(
    reservoir_draw,
    loss_fn=None,
    donate: bool = True,
    precision=None,
    state_sharding=None,
    buffer_sharding=None,
    draw_constraint=None,
):
    """Build the one-dispatch echo step: gather + re-augmentation +
    loss + donated update in ONE jit.

    ``reservoir_draw`` is the traceable gather+augment body a
    :class:`blendjax.data.echo.SampleReservoir` exposes as
    :meth:`~blendjax.data.echo.SampleReservoir.draw` —
    ``fn(buffers, idx, counter) -> batch`` — the same hook pattern as
    ``state_sharding``/``superbatch_constraint``. Before this builder
    the echo path cost TWO device dispatches per step (reservoir
    gather+augment in one jit, train update in another), the only
    place the ``dispatch_per_step == 1.0`` contract from PR 3 didn't
    hold; here the draw happens INSIDE the train jit, so the echoed
    batch exists only as a fused-step intermediate — it never
    round-trips as a standalone ``jax.Array``, and the per-step device
    call count is exactly one.

    The returned ``step(state, batch)`` composes with
    :class:`blendjax.train.TrainDriver` unchanged: ``batch`` is the
    draw token ``EchoingPipeline(emit_draws=True)`` yields —
    ``{"_echo_buffers": ring pytree, "_echo_idx": host (B,) indices,
    "_echo_counter": host draw counter}``. The ring buffers pass as
    ORDINARY (non-donated) arguments: the reservoir still owns them,
    the gather only reads, and the runtime donation audit
    (:mod:`blendjax.testing.donation`) pins that their pointers stay
    stable across fused steps. A batch without ``_echo_idx`` (e.g. a
    mixed stream's fresh decoded batch) falls back to the plain
    per-batch supervised step — still one dispatch.

    ``buffer_sharding`` (mesh path) pins the ring's ``data``-axis
    layout into the jit's ``in_shardings`` (a single sharding applies
    as a pytree prefix over every ring field), and ``draw_constraint``
    re-shards the just-gathered batch over the batch axis inside the
    jit — the same two mesh hooks ``make_mesh_fused_step`` uses for
    packed groups. ``precision`` follows
    :func:`make_supervised_step`.
    """
    loss_fn = loss_fn or _default_loss
    policy = resolve_policy(precision)
    pin = draw_constraint or (lambda b: b)
    counted = counting(loss_fn)
    fallback = make_supervised_step(
        loss_fn=loss_fn, donate=donate, precision=precision,
        state_sharding=state_sharding,
    )

    def _fused(state, buffers, idx, counter):
        batch = pin(reservoir_draw(buffers, idx, counter))

        def scalar_loss(params):
            return counted(state, params, batch)

        (loss, counters), grads = policy_value_and_grad(
            scalar_loss, state.params, policy, has_aux=True
        )
        with jax.named_scope(SCOPE_OPTIMIZER):
            state = state.apply_gradients(grads=grads)
        return state, _step_metrics(loss, counters)

    jit_kwargs = _sharding_jit_kwargs(
        state_sharding, n_data_args=3,
        data_shardings=(
            {1: buffer_sharding} if buffer_sharding is not None else None
        ),
    )
    fused = jax.jit(
        _fused,
        donate_argnums=(0,) if donate else (),
        **jit_kwargs,
    )

    def step(state, batch):
        idx = batch.get("_echo_idx")
        if idx is None:
            fields = {
                k: v for k, v in batch.items()
                if not k.startswith("_") or k == "_mask"
            }
            return fallback(state, fields)
        return fused(
            state, batch["_echo_buffers"], idx, batch["_echo_counter"]
        )

    # same handles as make_fused_tile_step's dispatcher
    step.jits = {"echo": fused, "fallback": fallback}
    step._cache_size = lambda: sum(
        j._cache_size() for j in step.jits.values()
    )
    return step


def make_eval_step():
    def evaluate(state, batch):
        pred = state.apply_fn({"params": state.params}, batch["image"])
        mask = batch.get("_mask")
        err = jnp.linalg.norm(
            pred - batch["xy"].astype(jnp.float32), axis=-1
        )
        if mask is None:
            px_err = jnp.mean(err)
        else:
            # mask-padded tail batch: padded rows must not dilute the
            # eval metrics (an eval pass sees every real example once)
            m = mask.astype(jnp.float32)
            px_err = (
                err.reshape(err.shape[0], -1).mean(axis=1) * m
            ).sum() / jnp.maximum(m.sum(), 1.0)
        return {
            "loss": corner_loss(
                pred, batch["xy"], image_shape=batch["image"].shape[1:3],
                mask=mask,
            ),
            "px_err": px_err,
        }

    # pure read of the state (no update returned): donating it would
    # free params the caller still trains with
    # bjx: ignore[BJX112]
    return jax.jit(evaluate)
