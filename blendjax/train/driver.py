"""Async overlap driver: keep donated train-step dispatches in flight.

The live-loop gap this closes (BENCH_r05): ``mfu_step_alone`` 0.4724 vs
``mfu_live`` 0.0085 — a ~55x gap — because the consumer loop ran
dispatch-SYNC-dispatch: every step's loss was fetched (or its buffers
blocked on) before the next batch was even requested, and the on-device
decode dispatched as a separate jit call that serialized with the step.
With the decode fused into the step (``make_fused_tile_step``: exactly
one device dispatch per step) and this driver keeping up to ``inflight``
of those dispatches outstanding, H2D transfer, fused decode+step
compute, and host ingest all overlap; the host touches device results
only every ``sync_every`` steps and when the ring is genuinely full.

Rules of the hot loop (enforced by bjx-lint BJX106 on this module):
never host-sync a value dispatched in the same loop iteration —
completion is tracked per in-flight entry (non-blocking ``is_ready``
polls retire finished work), and blocking waits target the OLDEST
entry only, which was dispatched ``inflight`` steps ago and is usually
long done.
"""

from __future__ import annotations

# bjx: driver-hot-path (BJX106 flags same-iteration host syncs on step
# outputs inside this module's dispatch loops)

import collections
import logging
import threading
import time

import numpy as np

from blendjax.obs.devledger import RetraceAudit, default_peak_flops
from blendjax.obs.trace import (
    TERMINAL_STAGE,
    pop_traces as trace_pop,
    stage as trace_stage,
    tracer,
)
from blendjax.utils.metrics import SOWN_COUNTERS, metrics

logger = logging.getLogger(__name__)

_LOGGED_ONCE: set = set()


def _log_once(fn, msg: str, *args) -> None:
    """Per-process dedup for build-time knob advice — a bench that
    constructs dozens of drivers should name a missing knob once, not
    once per leg."""
    if msg not in _LOGGED_ONCE:
        _LOGGED_ONCE.add(msg)
        fn(msg, *args)


class TrainDriver:
    """Dispatch-ahead wrapper around a ``step(state, batch) ->
    (state, metrics)`` callable (any :mod:`blendjax.train.steps`
    builder; pair with :func:`make_fused_tile_step` +
    ``StreamDataPipeline(emit_packed=True)`` for the one-dispatch-per-
    step fused path).

    - ``inflight``: how many step dispatches may be outstanding. The
      ring is bounded by completion tracking, not serialization:
      finished entries retire via a non-blocking readiness poll, and the
      driver blocks (once, on the oldest entry) only when the ring is
      genuinely full of unfinished work. ``inflight=1`` reproduces the
      old dispatch-wait-dispatch loop for A/B comparison.
    - ``sync_every``: fetch one loss value to host every N steps (the
      oldest in flight — the least-blocking real number). 0 disables
      periodic syncs; :meth:`finish`/:meth:`drain` still fetch the final
      loss, which transitively syncs the whole donated-state chain.
    - ``pad_partial``: bucket-pad `_partial` tail batches that reach the
      driver unmasked (``blendjax.data.batcher.pad_to_bucket``), so a
      finite stream's ragged tail cannot recompile the step mid-run.
      Pipelines constructed with ``pad_partial=True`` (the default)
      already deliver masked bucket shapes and skip this path.

    Stats (:attr:`stats`): ``steps``/``dispatches`` (one device call per
    step on the fused path), ``inflight_hwm`` (steps-in-flight
    high-water mark), ``host_blocks`` (genuine ring-full waits — near
    zero when the device keeps up), ``syncs`` (periodic loss fetches).

    Device-timeline metrics: each ring entry is timed dispatch ->
    observed retirement (the moment the completion poll/fetch sees it
    done), feeding the ``train.step_device_ms`` histogram. That is an
    upper bound on per-step device latency and nothing tighter: with
    two or more steps in flight a step is dispatched while the one
    ahead of it still runs, so its reading includes the wait behind
    that step (about ``inflight`` x the device time on a step-bound
    run). Device time per step comes from a profiler trace
    (``benchmark/reduce_trace.py``), not from this histogram. Given
    ``flops_per_image`` (hand-fed, or derived by :meth:`build` from
    the device ledger's ``compiled.cost_analysis()`` entries —
    :mod:`blendjax.obs.devledger`) and ``peak_flops`` (explicit, or
    defaulted from the chip peaks table), retirements
    additionally maintain a live ``train.mfu`` gauge (retired
    images/s x flops_per_image / peak_flops over ~1 s windows), so
    MFU is an always-on run metric the SLO watchdog can bound, not
    just a bench artifact.
    """

    def __init__(self, step, state, inflight: int = 4,
                 sync_every: int = 32, pad_partial: bool = True,
                 buckets=None, flops_per_image: float | None = None,
                 peak_flops: float | None = None,
                 checkpoint=None, checkpoint_every: int = 0,
                 session_state=None, place=None):
        self.step = step
        self.state = state
        # Placement folded into the dispatch (docs/performance.md
        # "Closing the live-MFU gap", lever 3): when `place` is set —
        # typically ``pipeline.feeder.place`` with
        # ``StreamDataPipeline(place_in_driver=True)`` — submit()
        # receives HOST batches and commits the one grouped async
        # ``device_put`` right before the step dispatch, so the
        # transfer overlaps the in-flight steps this ring tracks
        # instead of running as a separate host-blocking feeder stage.
        # Retirement readiness already polls the step's global output,
        # which transitively covers the transfer.
        self.place = place
        self.inflight = max(1, int(inflight))
        self.sync_every = max(0, int(sync_every or 0))
        self.pad_partial = bool(pad_partial)
        self.buckets = buckets
        self.flops_per_image = (
            float(flops_per_image) if flops_per_image else None
        )
        self.peak_flops = float(peak_flops) if peak_flops else None
        # Where the MFU numerator came from: "hand-fed" (caller knob),
        # "cost-model" (build() derived it from the device ledger's
        # cost_analysis entries), or None (gauge off).
        self.mfu_source = "hand-fed" if self.flops_per_image else None
        self._resolve_peak_flops()
        # Retrace audit (blendjax.obs.devledger): watches the step's
        # jit dispatch cache per submit — on the AOT path that is the
        # fallback jit, so any growth IS the unbucketed-shape signal.
        # None when the step isn't a watchable jit wrapper.
        self.retrace_audit = RetraceAudit.for_step(step)
        # Checkpointing (blendjax.checkpoint, docs/checkpointing.md):
        # every `checkpoint_every` steps — and whenever
        # request_checkpoint() was called from any thread — submit()
        # hands the freshly-retired state to the SnapshotManager at
        # the step boundary. save_async clones the device leaves
        # before returning, so the NEXT dispatch's donation can never
        # invalidate a snapshot mid-write, and the serialization runs
        # on the manager's own thread: ckpt.save_ms never lands
        # inside a step dispatch.
        self.checkpoint = checkpoint
        self.checkpoint_every = max(0, int(checkpoint_every or 0))
        self.session_state = session_state
        self.checkpoints = 0
        self._ckpt_request = threading.Event()
        # A PreemptionGuard (blendjax.checkpoint.preempt) attaches
        # itself here; submit() honors the flag at the next step
        # boundary with a drain + synchronous snapshot.
        self.preempt = None
        # ring entries: [loss, t_dispatch_mono, images, traces, counters]
        self._pending: collections.deque = collections.deque()
        self.losses: list = []
        self.steps = 0
        self.dispatches = 0
        self.inflight_hwm = 0
        self.host_blocks = 0
        self.images_retired = 0
        self._mfu_mark: tuple | None = None  # (t_mono, images_retired)
        self._t_first_dispatch: float | None = None
        # Cold-start accounting (docs/performance.md "Instant start"):
        # build() stamps startup_ms (model init + step AOT-compile wall
        # time); the first retirement stamps time_to_first_step_ms
        # relative to construction. Both surface in `stats` and the
        # live_start bench row, where the warm-vs-cold persistent-cache
        # ratio is CI-gated.
        self._t_created = time.monotonic()
        self._t_first_retire: float | None = None
        self.startup_ms: float | None = None

    def _resolve_peak_flops(self) -> None:
        """The ``train.mfu`` gauge needs BOTH knobs: the denominator
        defaults from the chip peaks table (x ``self.chips`` on mesh
        drivers). An accelerator the table does not hold raises
        (:func:`blendjax.obs.devledger.chip_peak_flops`); on the CPU
        backend there is no chip, and the gauge stays off with one log
        line naming the missing knob."""
        if not self.flops_per_image or self.peak_flops:
            return
        chips = max(1, int(getattr(self, "chips", 1) or 1))
        default = default_peak_flops()
        if default:
            peak, label = default
            self.peak_flops = peak * chips
            _log_once(
                logger.info,
                "train.mfu: peak_flops defaulted to %.4g "
                "(%s peak x %d chip(s))",
                self.peak_flops, label, chips,
            )
        else:
            _log_once(
                logger.warning,
                "train.mfu gauge disabled: flops_per_image is set but "
                "peak_flops=None and the CPU backend has no chip peak — "
                "pass peak_flops= to the driver",
            )

    @classmethod
    def build(cls, model, example_batch, *, loss_fn=None, optimizer=None,
              learning_rate: float = 1e-3, rng=None, augment=None,
              augment_rng=None, precision=None, aot: bool = True,
              aot_cache_dir: str | None = None, resume: bool = False,
              **driver_kwargs):
        """Model -> ready driver, with the step set AOT-compiled.

        One call covers init, restore, and warm-up: ``make_train_state``
        from ``example_batch["image"]``, an optional checkpoint restore
        (``resume=True`` with ``checkpoint=`` in ``driver_kwargs`` —
        restored driver counters are loaded and the session dict is left
        on ``driver.resumed_session`` for the caller's lineage restore),
        then ``blendjax.train.aot.build_aot_step`` compiles every
        bucket-ladder shape before step 0 — behind the persistent
        compilation cache when ``aot_cache_dir`` is set, so elastic
        resume and preemption churn pay milliseconds, not re-trace
        time. The total build wall time lands on ``driver.startup_ms``.
        """
        from blendjax.train.steps import (
            make_supervised_step,
            make_train_state,
        )

        t0 = time.monotonic()
        if not isinstance(example_batch, dict) or "image" not in example_batch:
            raise TypeError(
                "build() needs a full example batch dict (at least "
                "'image' + the loss's fields) to derive the AOT ladder"
            )
        state = make_train_state(
            model, example_batch["image"], optimizer=optimizer,
            learning_rate=learning_rate, rng=rng,
        )
        session = None
        mgr = driver_kwargs.get("checkpoint")
        if resume and mgr is not None:
            restored = mgr.restore(state)
            if restored is not None:
                state = restored.state
                session = restored.session
        step = make_supervised_step(
            loss_fn=loss_fn, augment=augment, augment_rng=augment_rng,
            precision=precision,
        )
        if aot:
            from blendjax.train.aot import build_aot_step, cache_key

            buckets = driver_kwargs.get("buckets")
            step = build_aot_step(
                step, state, example_batch, buckets=buckets,
                cache_dir=aot_cache_dir,
                key=cache_key(
                    model=model, precision=precision, buckets=buckets,
                ) if aot_cache_dir else None,
                ledger_name=f"{type(model).__name__}.supervised_step",
            )
        drv = cls(step, state, **driver_kwargs)
        drv._adopt_cost_model_flops(step, example_batch)
        drv._t_created = t0  # cold-start clock starts at build entry
        drv.startup_ms = (time.monotonic() - t0) * 1e3
        drv.resumed_session = session
        if isinstance(session, dict) and session.get("driver"):
            drv.load_state_dict(session["driver"])
        return drv

    def _adopt_cost_model_flops(self, step, example_batch,
                                entries=None) -> None:
        """Cost-model MFU numerator from the device ledger: when the
        caller hand-fed no ``flops_per_image``, the AOT build's ledger
        entries already hold XLA's own FLOPs count per signature — use
        the full-batch entry's flops / batch as the numerator (hand-fed
        stays the override). Accounting only; never fails a build."""
        if self.flops_per_image:
            return
        try:
            if entries is None:
                entries = getattr(step, "ledger_entries", None) or []
            entries = [
                e for e in entries
                if isinstance(e.get("flops"), float) and e.get("batch_images")
            ]
            if not entries:
                return
            lead = int(np.shape(example_batch["image"])[0])
            match = [e for e in entries if e["batch_images"] == lead]
            e = max(match or entries, key=lambda e: e["batch_images"])
            # cost_analysis() counts the PER-DEVICE partitioned program;
            # on a mesh the global batch spreads over `chips` devices,
            # so total flops per image is per-device flops x chips /
            # global batch (chips=1 single-chip: a plain ratio)
            chips = max(1, int(getattr(self, "chips", 1) or 1))
            self.flops_per_image = e["flops"] * chips / e["batch_images"]
            self.mfu_source = "cost-model"
            self._resolve_peak_flops()
        except Exception:  # pragma: no cover - accounting-only path
            logger.debug("cost-model flops adoption failed", exc_info=True)

    # -- ring ----------------------------------------------------------------

    @staticmethod
    def _is_done(arr) -> bool:
        """Non-blocking readiness poll (shared definition:
        :func:`blendjax.utils.device.transfer_done`)."""
        from blendjax.utils.device import transfer_done

        return transfer_done(arr)

    def _retire(self, entry) -> None:
        """Account one completed ring entry: the dispatch->retirement
        device-timeline histogram, the live MFU gauge, the terminal
        stamp of any frame trace riding the entry, and the step's
        in-program counts, booked under their registry names
        (``SOWN_COUNTERS``). Host bookkeeping only — the loss value
        itself is NOT fetched here, and the counts, outputs of a program
        that has finished, were copied to the host since its dispatch."""
        _loss, t0, images, traces, counters = entry
        now = time.monotonic()
        if self._t_first_retire is None:
            self._t_first_retire = now
        metrics.observe("train.step_device_ms", (now - t0) * 1e3)
        self.images_retired += images
        if self.flops_per_image and self.peak_flops:
            if self._mfu_mark is None:
                self._mfu_mark = (now, self.images_retired)
            else:
                t_mark, img_mark = self._mfu_mark
                dt = now - t_mark
                if dt >= 1.0:
                    rate = (self.images_retired - img_mark) / dt
                    metrics.gauge(
                        "train.mfu",
                        round(
                            rate * self.flops_per_image / self.peak_flops,
                            6,
                        ),
                    )
                    self._mfu_mark = (now, self.images_retired)
        if traces:
            for tr in traces:
                trace_stage(tr, TERMINAL_STAGE)
                tracer.complete(tr)
        if counters:
            for sown, name in SOWN_COUNTERS:
                if sown in counters:
                    metrics.count(name, int(counters[sown]))

    def _block_oldest(self) -> None:
        """Retire the oldest in-flight entry, blocking if needed. A
        block is counted only when genuine (the entry wasn't already
        done): with overlap working, the entry ``inflight`` steps back
        has finished and this is a free pop."""
        import jax

        entry = self._pending.popleft()
        if not self._is_done(entry[0]):
            self.host_blocks += 1
            # Registry mirror of the instance stat: the stall doctor
            # (blendjax.obs.doctor) reads plain metrics snapshots, and
            # a genuine ring-full block is its strongest step-bound
            # signal.
            metrics.count("train.host_blocks")
            with metrics.span("driver.ring_wait"):
                jax.block_until_ready(entry[0])
        self._retire(entry)

    def _sync_oldest(self) -> None:
        """Periodic loss fetch (the designed host-sync point): the
        OLDEST in-flight loss — a real training signal that blocks the
        least, because everything newer stays dispatched."""
        if not self._pending:
            return
        entry = self._pending.popleft()
        with metrics.span("driver.loss_sync"):
            self.losses.append(
                float(np.asarray(entry[0]).reshape(-1)[-1])
            )
        self._retire(entry)

    # -- dispatch ------------------------------------------------------------

    @staticmethod
    def _batch_images(batch) -> int:
        """Images this batch trains on — for the MFU gauge. Packed
        chunk groups count K' rows x the per-batch lead from `_spec`;
        decoded (K, B, H, W, C) superbatches count K*B; plain batches
        their leading dim. Shape reads only — no device values."""
        idx = batch.get("_echo_idx")
        if idx is None:
            idx = batch.get("_rl_idx")
        if idx is not None:
            # fused draw token (echo or RL replay): the host index
            # vector names every sample the step trains on (the gather
            # runs inside the jit)
            return int(len(idx))
        packed = batch.get("_packed")
        if packed is not None:
            spec = batch.get("_spec") or ()
            lead = next(
                (s[0] for n, _d, s, *_r in spec if n == "xy"), None
            )
            if lead is None:
                lead = max(
                    (s[0] for _n, _d, s, *_r in spec if s), default=1
                )
            return int(packed.shape[0]) * int(lead)
        img = batch.get("image")
        if img is not None and getattr(img, "ndim", 0) >= 4:
            shp = img.shape
            return int(shp[0] * shp[1]) if img.ndim >= 5 else int(shp[0])
        lead = next(
            (
                v.shape[0] for k, v in batch.items()
                if not k.startswith("_") and getattr(v, "ndim", 0) >= 1
            ),
            0,
        )
        return int(lead)

    def ensure_ring_slot(self) -> None:
        """Retire finished in-flight entries (non-blocking completion
        poll) and, when the ring is genuinely full, block on the
        oldest until a slot frees. ``submit`` runs this before every
        dispatch; callers that must not hold a lock across a device
        wait (the RL learner holds the reservoir lock across its
        dispatch) call it themselves FIRST, so the locked section
        contains only the async dispatch enqueue."""
        pending = self._pending
        while pending and self._is_done(pending[0][0]):
            self._retire(pending.popleft())  # completion tracking
        while len(pending) >= self.inflight:
            self._block_oldest()

    def submit(self, batch, post: bool = True) -> None:
        """Dispatch one step without waiting on its result. ``post``
        controls whether the cadenced step-boundary work
        (:meth:`post_dispatch`) runs before returning — callers that
        dispatch inside a critical section pass ``post=False`` and run
        it themselves after releasing the lock."""
        if self.preempt is not None and self.preempt.requested:
            self._preempt_flush()
        if (
            self.pad_partial and batch.get("_partial")
            and "_mask" not in batch
        ):
            from blendjax.data.batcher import pad_to_bucket

            batch = pad_to_bucket(batch, buckets=self.buckets)
        if self.place is not None:
            # Free a ring slot FIRST so at most `inflight` transfer+step
            # pairs are outstanding, then commit the grouped async
            # placement — it overlaps every older in-flight dispatch.
            # Runs before the trace pop below so the "place" stamp
            # precedes "step_dispatch" like it does on the feeder path.
            self.ensure_ring_slot()
            batch = self.place(batch)
        # Frame traces must come OFF the batch before the step call:
        # a trace dict is host-side metadata no jit can consume (the
        # same contract as `_meta`, which the step builders filter).
        traces = trace_pop(batch)
        if traces:
            for tr in traces:
                trace_stage(tr, "step_dispatch")
        # Scenario stamps (blendjax.scenario) are the same kind of
        # host-side sidecar: string/None leaves a jit flattens and
        # rejects. The eager echo path attaches per-row stamps to
        # SAMPLE batches (the fused token path filters keys itself),
        # so pop them here — accounting reads them BEFORE submit.
        if "_scenario_rows" in batch or "_scenario" in batch:
            batch = {
                k: v for k, v in batch.items()
                if k not in ("_scenario_rows", "_scenario")
            }
        images = self._batch_images(batch)
        if self._t_first_dispatch is None:
            self._t_first_dispatch = time.monotonic()
        self.ensure_ring_slot()
        with metrics.span("train.dispatch"):
            self.state, m = self.step(self.state, batch)
        metrics.count("train.dispatches")
        if self.retrace_audit is not None:
            # cache-size delta AFTER the dispatch: growth past warm-up
            # counts device.retraces and attributes this batch signature
            self.retrace_audit.observe(batch)
        self.dispatches += 1
        self.steps += 1
        counters = m.get("counters")
        if counters:
            for v in counters.values():
                v.copy_to_host_async()  # read at retirement, no wait there
        pending = self._pending
        pending.append(
            [m["loss"], time.monotonic(), images, traces, counters]
        )
        if len(pending) > self.inflight_hwm:
            self.inflight_hwm = len(pending)
        # Registry mirror runs UNCONDITIONALLY (gauge_max is already a
        # no-op when not a new high): gating it on instance-hwm growth
        # meant a metrics.reset() mid-run (bench's measured-window
        # reset) silently lost the gauge forever — the instance hwm,
        # pinned during warmup, never grew again.
        metrics.gauge_max("train.inflight_hwm", len(pending))
        if post:
            self.post_dispatch()

    def post_dispatch(self) -> None:
        """The cadenced step-boundary work ``submit`` runs after each
        dispatch: the periodic loss fetch (a BLOCKING d2h of the
        oldest in-flight value) and the checkpoint hand-off (a
        session-state collection + device clones). Factored out so
        callers that dispatch under a lock (the RL learner holds the
        reservoir lock across its dispatch enqueue) can run this part
        OUTSIDE it — neither belongs in a critical section another
        thread waits on."""
        if self.sync_every and self.steps % self.sync_every == 0:
            self._sync_oldest()
        if self.checkpoint is not None and (
            self._ckpt_request.is_set()
            or (
                self.checkpoint_every
                and self.steps % self.checkpoint_every == 0
            )
        ):
            self._ckpt_request.clear()
            self._dispatch_checkpoint()

    # -- checkpointing ---------------------------------------------------------

    def request_checkpoint(self) -> None:
        """Thread-safe: snapshot at the NEXT step boundary (the SLO
        watchdog's checkpoint-on-breach arm calls this from the
        reporter thread — the save itself still happens at
        retirement, never mid-flight)."""
        self._ckpt_request.set()

    def _dispatch_checkpoint(self) -> None:
        """Hand the current state + session to the SnapshotManager.
        Async by design: device leaves are cloned before this returns
        (a handful of non-train dispatches), the d2h + file writes run
        on the manager's writer thread."""
        session = {}
        if callable(self.session_state):
            session = dict(self.session_state() or {})
        session.setdefault("driver", self.state_dict())
        self.checkpoint.save_async(
            self.steps, self.state, session=session
        )
        self.checkpoints += 1

    def _preempt_flush(self) -> None:
        """The SIGTERM path: drain the ring (every in-flight dispatch
        retires — donated buffers settle), snapshot the final state,
        block until it commits, then raise for the run loop to exit.
        See blendjax.checkpoint.preempt."""
        from blendjax.checkpoint.preempt import PreemptionRequested

        self.drain()
        outcome = "no checkpoint manager attached"
        if self.checkpoint is not None:
            self._dispatch_checkpoint()
            # The one sanctioned synchronous checkpoint wait on the hot
            # path: the process is exiting on a preemption deadline —
            # an un-flushed async write would race interpreter teardown.
            # bjx: ignore[BJX114]
            self.checkpoint.wait()
            # the writer never raises into the train loop, so silence
            # is not evidence: report what actually landed — a
            # scheduler that believes a failed flush committed loses
            # every step since the last cadence save
            err = getattr(self.checkpoint, "last_error", None)
            outcome = (
                f"snapshot FAILED ({err!r}) — resuming from the last "
                "committed step" if err is not None
                else "snapshot committed"
            )
        metrics.count("ckpt.preemptions")
        raise PreemptionRequested(
            f"preemption honored at step {self.steps}: {outcome}"
        )

    def checkpoint_now(self, wait: bool = True) -> None:
        """Synchronous out-of-band snapshot (teardown / eval
        boundaries): drain the ring, snapshot, optionally block until
        committed. NOT for the hot loop — cadence saves go through
        ``checkpoint_every``/``request_checkpoint`` and stay async."""
        if self.checkpoint is None:
            raise RuntimeError("no checkpoint manager attached")
        self.drain()
        self._dispatch_checkpoint()
        if wait:
            # teardown flush, same justification as _preempt_flush
            # bjx: ignore[BJX114]
            self.checkpoint.wait()
            err = getattr(self.checkpoint, "last_error", None)
            if err is not None:
                raise RuntimeError(
                    f"checkpoint_now: snapshot write failed: {err!r}"
                ) from err

    #: Loss-history tail kept in the session snapshot: continuity only
    #: needs the step counters (cadence alignment), so bounding the
    #: tail keeps per-snapshot work and session size O(1) over a long
    #: run instead of re-serializing an ever-growing list every save.
    LOSS_TAIL = 4096

    def state_dict(self) -> dict:
        """Driver counters for the session snapshot: a resumed driver
        continues the same step numbering, so sync/checkpoint cadence
        and the augment key folds (keyed by ``state.step``) line up
        with the uninterrupted run."""
        tail = self.losses[-self.LOSS_TAIL:]
        return {
            "steps": self.steps,
            "dispatches": self.dispatches,
            "images_retired": self.images_retired,
            "checkpoints": self.checkpoints,
            "losses": [float(v) for v in tail],
            "losses_total": len(self.losses),
        }

    def load_state_dict(self, d: dict) -> None:
        self.steps = int(d["steps"])
        self.dispatches = int(d.get("dispatches", d["steps"]))
        self.images_retired = int(d.get("images_retired", 0))
        self.checkpoints = int(d.get("checkpoints", 0))
        self.losses = [float(v) for v in d.get("losses", [])]

    def drain(self):
        """Block until every dispatched step completed and return the
        newest loss value (the d2h fetch transitively syncs the whole
        donated-state chain — the one sync honest on every backend;
        see docs/performance.md measurement hygiene)."""
        if not self._pending:
            return self.losses[-1] if self.losses else None
        newest = self._pending[-1]
        # with driver.ring_wait and driver.loss_sync, the whole of the
        # host's wait for the device
        with metrics.span("driver.drain_wait"):
            val = float(np.asarray(newest[0]).reshape(-1)[-1])
        # the fetch transitively completed every older entry: retire
        # them all (device-timeline accounting + trace terminal stamps)
        while self._pending:
            self._retire(self._pending.popleft())
        # Whole-run MFU at the drain barrier: the windowed gauge in
        # _retire needs >=1 s between retirements, so a short run (or
        # a drain landing mid-window) would otherwise end without one.
        if (
            self.flops_per_image and self.peak_flops
            and self.images_retired and self._t_first_dispatch is not None
        ):
            dt = max(time.monotonic() - self._t_first_dispatch, 1e-9)
            metrics.gauge(
                "train.mfu",
                round(
                    (self.images_retired / dt) * self.flops_per_image
                    / self.peak_flops,
                    6,
                ),
            )
        self.losses.append(val)
        return val

    def finish(self):
        """Drain and return ``(state, final_loss)``."""
        return self.state, self.drain()

    def run(self, batches, max_steps: int | None = None):
        """Drive a batch iterable end to end; returns
        ``(state, final_loss)``."""
        for batch in batches:
            self.submit(batch)
            if max_steps is not None and self.steps >= max_steps:
                break
        return self.finish()

    @property
    def time_to_first_step_ms(self) -> float | None:
        """Wall time from driver construction to the first retired step
        (``None`` until one retires) — the end-to-end cold-start number
        the ``live_start`` bench row gates warm-vs-cold."""
        if self._t_first_retire is None:
            return None
        return (self._t_first_retire - self._t_created) * 1e3

    @property
    def stats(self) -> dict:
        return {
            "steps": self.steps,
            "dispatches": self.dispatches,
            "inflight": self.inflight,
            "inflight_hwm": self.inflight_hwm,
            "host_blocks": self.host_blocks,
            "syncs": len(self.losses),
            "images_retired": self.images_retired,
            "checkpoints": self.checkpoints,
            "startup_ms": self.startup_ms,
            "time_to_first_step_ms": self.time_to_first_step_ms,
            "mfu_source": self.mfu_source,
        }
