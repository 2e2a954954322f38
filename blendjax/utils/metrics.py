"""Lightweight metrics: counters, gauges, exact histograms, timing spans.

The reference has no metrics system (SURVEY.md §5 — only wall-clock in its
benchmark harness); blendjax instruments the whole producer → wire →
ingest → train pipeline so feed stalls are diagnosable: per-stage spans
feed lock-exact log-bucketed histograms (p50/p95/p99, not just means —
the mean hides exactly the tail a stall doctor needs), queue-depth
gauges, and a one-line report. ``blendjax.obs`` builds the cross-process
layer on top: frame lineage, the stall doctor, and the Prometheus /
JSONL / Chrome-trace exporters. For deep device-side dives, ``trace``
wraps ``jax.profiler.trace`` so the same code path emits a
TensorBoard-loadable profile.
"""

from __future__ import annotations

import contextlib
import math
import sys
import threading
import time
from collections import defaultdict, deque

from blendjax.utils.tg import guard

# 8 buckets per octave: bucket bounds grow by 2**(1/8) ≈ 9.05%, so a
# quantile read from the bucket midpoint is within ~4.4% of the true
# value — tight enough to tell a 2x tail regression apart, cheap enough
# (one log + one dict bump) for the ingest hot path.
_GAMMA = 2.0 ** 0.125
_LOG_GAMMA = math.log(_GAMMA)


# The device-side vocabulary: the ``jax.named_scope`` names the step's
# parts carry where they run, so that every device operation in a
# profiler trace says which part it belongs to (its ``tf_op`` stat is
# the name stack: ``jit(_fused)/decode/vmap(vmap(palette_expand))/select_n``).
# Forward and backward need no scope of ours: ``jvp(<Model>)`` and
# ``transpose(jvp(<Model>))`` are already on the stack. Documented in
# docs/observability.md ("Device scopes"); read by benchmark/trace_scopes.py.
SCOPE_DECODE = "decode"            # packed bytes -> decoded superbatch
SCOPE_PALETTE_EXPAND = "palette_expand"  # inside decode: palette -> RGBA
SCOPE_RESHARD = "reshard"          # mesh path: re-shard the decoded batch
SCOPE_OPTIMIZER = "optimizer"      # apply_gradients
SCOPE_ATTN_CORE = "attn_core"      # scores -> softmax -> weighted sum
# StreamFormer's input side, frames -> patch tokens: the flax module's own
# name (``jvp(StreamFormer)/patch_embed/dot_general``), no named_scope
SCOPE_PATCH_EMBED = "patch_embed"
# StreamHybrid's mixers (models/hybrid.py, models/moe.py, ops/ssd.py): a
# Mamba-2 layer's whole mixer and, inside it, the state-space scan alone;
# an expert layer's whole mixer and, inside it, the router with the
# selection and the per-expert gate, the held experts' two products with
# their activation, and the shared expert
SCOPE_SSM_MIXER = "ssm_mixer"
SCOPE_SSD = "ssd"
SCOPE_MOE = "moe"
SCOPE_MOE_ROUTE = "moe_route"
SCOPE_MOE_EXPERTS = "moe_experts"
SCOPE_MOE_SHARED = "moe_shared"
# StreamMoEFormer's attention sub-block (models/moe_former.py): the
# projections, q/k norms, rotary embedding, the core, the output gate
# and the output projection, without the sandwich norms around them
SCOPE_ATTN_BLOCK = "attn_block"
# StreamDeltaHybrid's linear-attention layers (models/delta_hybrid.py,
# ops/delta_rule.py): a gated delta-rule layer's whole mixer and, inside
# it, the chunked recurrence alone
SCOPE_GDN_MIXER = "gdn_mixer"
SCOPE_DELTA_RULE = "delta_rule"
STEP_SCOPES = (
    SCOPE_DECODE, SCOPE_PALETTE_EXPAND, SCOPE_RESHARD, SCOPE_OPTIMIZER,
    SCOPE_ATTN_CORE, SCOPE_PATCH_EMBED, SCOPE_SSM_MIXER, SCOPE_SSD,
    SCOPE_MOE, SCOPE_MOE_ROUTE, SCOPE_MOE_EXPERTS, SCOPE_MOE_SHARED,
    SCOPE_ATTN_BLOCK, SCOPE_GDN_MIXER, SCOPE_DELTA_RULE,
)
# The residuals StreamHybrid's layers keep through ``remat``: the outputs
# of their large products, which the backward reads and plain ``remat``
# would compute again (``jax.ad_checkpoint.checkpoint_name`` on each, the
# policy ``save_only_these_names(*SAVED_RESIDUALS)``): the held experts'
# up-product (float32), the shared expert's hidden pre-activation and the
# Mamba-2 input projection; and the scan kernel's output with the state
# each chunk starts from, so that its forward runs once a layer. Gated
# experts (StreamMoEFormer's) keep their gate products beside the up ones.
RESIDUAL_EXPERTS_UP = "moe_experts_up"
RESIDUAL_SHARED_UP = "moe_shared_up"
RESIDUAL_IN_PROJ = "ssm_in_proj"
RESIDUAL_SSD_Y = "ssd_y"
RESIDUAL_SSD_STATES = "ssd_states"
RESIDUAL_EXPERTS_GATE = "moe_experts_gate"
RESIDUAL_SHARED_GATE = "moe_shared_gate"
SAVED_RESIDUALS = (
    RESIDUAL_EXPERTS_UP, RESIDUAL_SHARED_UP, RESIDUAL_IN_PROJ,
    RESIDUAL_SSD_Y, RESIDUAL_SSD_STATES, RESIDUAL_EXPERTS_GATE,
    RESIDUAL_SHARED_GATE,
)
# What StreamMoEFormer's layers keep beside SAVED_RESIDUALS (its policy
# is ``save_only_these_names(*SAVED_RESIDUALS, *DECODER_RESIDUALS)``), so
# that the backward recomputes only elementwise work: the three-tensor
# attention kernel's output and its log-sum-exp (one column a head), so
# that the kernel's forward runs once a layer; the q, k, v and output
# gate projections' products, before the q/k norms, RoPE, the K/V repeat
# and the gate's sigmoid, which are recomputed from them; the gated heads'
# output, which ``proj`` and its weight gradient read; the dense SwiGLU
# layers' up and gate products; and the output of the attention sub-block
# and of a dense one, which its sandwich post-norm reads (else the
# backward computes ``proj`` and the dense down-product again for it).
# Where an elementwise value is left to the backward, XLA may fold it
# into a product's operand and compute it again for every tile of the
# product: so the gated output is kept, and an expert layer's output is
# not, since then the down-weight gradient's product read the float32 up
# and gate products tile by tile, which cost more than the down-product
# it saves (measured on one TPU v5e). StreamHybrid's one attention layer
# names its kernel's two, its q, k, v and its output as well, which its
# policy does not keep.
RESIDUAL_ATTN_O = "attn_o"
RESIDUAL_ATTN_LSE = "attn_lse"
RESIDUAL_ATTN_Q = "attn_q"
RESIDUAL_ATTN_K = "attn_k"
RESIDUAL_ATTN_V = "attn_v"
RESIDUAL_ATTN_GATE = "attn_gate"
RESIDUAL_ATTN_GATED = "attn_gated"
RESIDUAL_DENSE_UP = "dense_up"
RESIDUAL_DENSE_GATE = "dense_gate"
RESIDUAL_ATTN_OUT = "attn_out"
RESIDUAL_DENSE_OUT = "dense_out"
DECODER_RESIDUALS = (
    RESIDUAL_ATTN_O, RESIDUAL_ATTN_LSE, RESIDUAL_ATTN_Q, RESIDUAL_ATTN_K,
    RESIDUAL_ATTN_V, RESIDUAL_ATTN_GATE, RESIDUAL_ATTN_GATED,
    RESIDUAL_DENSE_UP, RESIDUAL_DENSE_GATE, RESIDUAL_ATTN_OUT,
    RESIDUAL_DENSE_OUT,
)
# What StreamDeltaHybrid's layers keep beside DECODER_RESIDUALS (its
# policy is ``save_only_these_names(*DECODER_RESIDUALS,
# *DELTA_RESIDUALS)``): a gated delta-rule mixer's q, k, v and output
# gate products, before their convolutions, norms and activations, which
# are recomputed from them; the recurrence's output; the state each chunk
# starts from and each chunk's (I + A)^-1, which the recurrence kernel's
# forward writes for its backward (so that the forward kernel runs once
# a layer and the backward inverts nothing); and the mixer's output,
# which the post-norm reads.
RESIDUAL_GDN_Q = "gdn_q"
RESIDUAL_GDN_K = "gdn_k"
RESIDUAL_GDN_V = "gdn_v"
RESIDUAL_GDN_GATE = "gdn_gate"
RESIDUAL_GDN_O = "gdn_o"
RESIDUAL_GDN_STATES = "gdn_states"
RESIDUAL_GDN_INVERSE = "gdn_inverse"
RESIDUAL_GDN_OUT = "gdn_out"
DELTA_RESIDUALS = (
    RESIDUAL_GDN_Q, RESIDUAL_GDN_K, RESIDUAL_GDN_V, RESIDUAL_GDN_GATE,
    RESIDUAL_GDN_O, RESIDUAL_GDN_STATES, RESIDUAL_GDN_INVERSE,
    RESIDUAL_GDN_OUT,
)
# The Pallas decode kernels: each is the ``name=`` of its ``pallas_call``
# and a scope around the call (inside ``decode``).
KERNEL_TILE_DECODE_SPATIAL = "tile_decode_spatial"
KERNEL_TILE_DECODE_SCATTER = "tile_decode_scatter"
# The fused attention core's forward and backward (inside ``attn_core``).
KERNEL_FLASH_FWD = "flash_attention_fwd"
KERNEL_FLASH_BWD = "flash_attention_bwd"
# The same pair over a sliding window: each query block reads only the
# key slab its band covers (inside ``attn_core``).
KERNEL_FLASH_WINDOW_FWD = "flash_attention_window_fwd"
KERNEL_FLASH_WINDOW_BWD = "flash_attention_window_bwd"
# The state-space scan's forward and backward (inside ``ssd``).
KERNEL_SSD_FWD = "ssd_scan_fwd"
KERNEL_SSD_BWD = "ssd_scan_bwd"
# The gated delta rule's forward and backward (inside ``delta_rule``).
KERNEL_DELTA_RULE_FWD = "delta_rule_fwd"
KERNEL_DELTA_RULE_BWD = "delta_rule_bwd"
KERNEL_NAMES = (
    KERNEL_TILE_DECODE_SPATIAL, KERNEL_TILE_DECODE_SCATTER,
    KERNEL_FLASH_FWD, KERNEL_FLASH_BWD, KERNEL_FLASH_WINDOW_FWD,
    KERNEL_FLASH_WINDOW_BWD, KERNEL_SSD_FWD, KERNEL_SSD_BWD,
    KERNEL_DELTA_RULE_FWD, KERNEL_DELTA_RULE_BWD,
)
# Counts made inside the step: the flax collection a model's layers sow
# integers into (models/moe.py ``RoutedExperts``), which the step
# builders return summed beside the loss (train/steps.py ``counting``),
# and the registry counter TrainDriver books each sown name under when
# its dispatch retires.
COUNTERS_COLLECTION = "counters"
SOWN_COUNTERS = (
    ("rows_held", "moe.rows_held"),
    ("rows_busiest_share", "moe.rows_busiest_share"),
    ("rows_even_share", "moe.rows_even_share"),
)


def saved_residual(x, name):
    """``x`` named ``name`` (one of SAVED_RESIDUALS, DECODER_RESIDUALS or
    DELTA_RESIDUALS) for the ``remat`` policy that keeps it; counted once
    a trace under ``remat.saved_residuals``. Outside ``remat`` the name
    changes nothing."""
    from jax.ad_checkpoint import checkpoint_name

    metrics.count("remat.saved_residuals")
    return checkpoint_name(x, name)


# ``jax.profiler.TraceAnnotation``, bound the first time a span opens
# in a process that has already imported jax: the program's spans then
# lie on the profiler's clock, in the trace's ``/host:CPU`` plane, over
# the device operations (one Perfetto file, no second export). Producer
# processes never import jax and pay one ``None`` test and one dict
# lookup a span; with the profiler off an annotation adds ~0.5 us.
_TraceAnnotation = None


def _bind_trace_annotation():
    global _TraceAnnotation
    _TraceAnnotation = getattr(
        sys.modules.get("jax.profiler"), "TraceAnnotation", None
    )
    return _TraceAnnotation


class Histogram:
    """Exact-count log-bucketed histogram.

    COUNTS are exact (every ``observe`` lands in exactly one bucket;
    bucket counts always sum to ``count`` — the property the bench's
    "histogram counts sum exactly to span counts" acceptance check
    rides on); VALUES are bucketed at ~9% geometric resolution, with
    exact ``min``/``max``/``sum`` kept alongside so p0/p100 and the
    mean never suffer bucketing error. Not self-locking: the owning
    :class:`Metrics` registry serializes access under its one lock.
    """

    __slots__ = (
        "count", "total", "min", "max", "zeros", "nonfinite", "buckets",
    )

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf
        # Non-positive observations (e.g. cross-host staleness under
        # clock skew) can't take a log: they get their own bucket below
        # every log bucket, so ordering — and therefore quantiles —
        # stays correct.
        self.zeros = 0
        # NaN/inf observations (a producer with a corrupted clock can
        # put one on the wire as a staleness input) are counted here
        # and otherwise ignored: math.log would raise and kill the
        # observing thread — the ingest loop, for lineage — over one
        # bad telemetry stamp.
        self.nonfinite = 0
        self.buckets: dict = {}

    def observe(self, value) -> None:
        v = float(value)
        if not math.isfinite(v):
            self.nonfinite += 1
            return
        self.count += 1
        self.total += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if v <= 0.0:
            self.zeros += 1
            return
        idx = math.floor(math.log(v) / _LOG_GAMMA)
        self.buckets[idx] = self.buckets.get(idx, 0) + 1

    def quantile(self, q: float) -> float:
        """Value at quantile ``q`` in [0, 1] (bucket-midpoint estimate,
        clamped to the exact observed [min, max])."""
        if self.count == 0:
            return 0.0
        if q <= 0.0:
            return self.min
        if q >= 1.0:
            return self.max
        rank = q * (self.count - 1)
        seen = self.zeros
        if rank < seen:
            return min(self.min, 0.0)
        for idx in sorted(self.buckets):
            seen += self.buckets[idx]
            if rank < seen:
                mid = _GAMMA ** (idx + 0.5)
                return min(max(mid, self.min), self.max)
        return self.max

    def summary(self) -> dict:
        if self.count == 0:
            out = {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                   "p50": 0.0, "p95": 0.0, "p99": 0.0}
            if self.nonfinite:
                out["nonfinite"] = self.nonfinite
            return out
        out = {
            "count": self.count,
            "sum": self.total,
            "min": self.min,
            "max": self.max,
            "p50": self.quantile(0.50),
            "p95": self.quantile(0.95),
            "p99": self.quantile(0.99),
        }
        if self.nonfinite:
            out["nonfinite"] = self.nonfinite
        return out

    def state_dict(self) -> dict:
        """Snapshot for the session store (blendjax.checkpoint):
        exact counts + bucket map; min/max only when observed (±inf
        sentinels don't belong in a wire document)."""
        d = {
            "count": self.count,
            "sum": self.total,
            "zeros": self.zeros,
            "nonfinite": self.nonfinite,
            "buckets": dict(self.buckets),
        }
        if self.count:
            d["min"] = self.min
            d["max"] = self.max
        return d

    def load_state_dict(self, d: dict) -> None:
        self.count = int(d["count"])
        self.total = float(d["sum"])
        self.zeros = int(d.get("zeros", 0))
        self.nonfinite = int(d.get("nonfinite", 0))
        self.buckets = {int(k): int(v) for k, v in d["buckets"].items()}
        self.min = float(d["min"]) if "min" in d else math.inf
        self.max = float(d["max"]) if "max" in d else -math.inf

    def cumulative_buckets(self) -> list:
        """``(upper_bound, cumulative_count)`` pairs in ascending bound
        order — the Prometheus histogram exposition shape (the exporter
        appends the implicit ``+Inf`` bucket itself)."""
        out = []
        cum = self.zeros
        if self.zeros:
            out.append((0.0, cum))
        for idx in sorted(self.buckets):
            cum += self.buckets[idx]
            out.append((_GAMMA ** (idx + 1), cum))
        return out


# bjx: thread-shared (every thread in the process reports here; one
# `_lock` makes each snapshot/update consistent — BJX117)
class Metrics:
    """Process-local registry. Thread-safe AND snapshot-exact: every
    mutation — counters, gauges, spans, histograms — runs under one
    lock (uncontended CPython lock acquire is ~100 ns — noise next to
    the per-batch work being counted, and the sharded ingest pool's
    ``wire.*``/``ingest.*`` pairs must sum EXACTLY, not approximately,
    for the bench's compression/throughput evidence), and ``report()``
    reads a consistent snapshot under the same lock (a lock-free read
    raced worker mutation: torn gauge snapshots and a possible
    ``RuntimeError: dictionary changed size during iteration``).
    """

    def __init__(self):
        self._lock = threading.Lock()
        # threadguard wiring (blendjax.utils.tg): under
        # BLENDJAX_THREADGUARD=1 any MUTATION of these tables without
        # `_lock` held raises at the access site; disabled, guard() is
        # identity and the registry is exactly as before. The read-only
        # dict surface of the two public tables stays exempt: tests and
        # debug code read counters after quiescing, and the consistent-
        # snapshot path is report(), not the raw dict.
        reads = (
            "get", "keys", "items", "values", "copy",
            "__getitem__", "__iter__", "__len__", "__contains__",
        )
        self.counters: dict = guard(
            defaultdict(int), name="metrics.counters", lock=self._lock,
            exempt=reads,
        )
        self.gauges: dict = guard(
            {}, name="metrics.gauges", lock=self._lock, exempt=reads,
        )
        self._spans: dict = guard(  # count, total_s
            defaultdict(lambda: [0, 0.0]), name="metrics.spans",
            lock=self._lock,
        )
        self._hists: dict = guard(
            defaultdict(Histogram), name="metrics.hists", lock=self._lock
        )
        # Optional per-span event ring for Chrome-trace export
        # (blendjax.obs.exporters.write_chrome_trace): disabled by
        # default — aggregates are always on, events are opt-in.
        self._events: deque | None = None

    def count(self, name: str, n: int = 1) -> None:
        # `dict[k] += n` is load/add/store bytecode — two workers
        # interleaving it lose increments. The lock makes the pair of
        # counters the bench ratios (compressed vs raw) exact.
        with self._lock:
            self.counters[name] += n

    def counter_value(self, name: str) -> int:
        """Locked read of one counter's current value — for writers
        that derive a gauge from counters they also emit (the value
        then stays consistent with the counters in the same snapshot,
        across any ``reset()``)."""
        with self._lock:
            return self.counters.get(name, 0)

    def gauge(self, name: str, value) -> None:
        # Locked like everything else: a bare dict store is GIL-atomic,
        # but report()'s consistent snapshot needs writers excluded.
        with self._lock:
            self.gauges[name] = value

    def gauge_max(self, name: str, value) -> None:
        # High-water-mark gauge: read-max-store is a lost-update race
        # for concurrent writers (the sharded ingest pool), so the pair
        # runs under the counter lock.
        with self._lock:
            if value > self.gauges.get(name, value - 1):
                self.gauges[name] = value

    def observe(self, name: str, value) -> None:
        """Record one sample into the named histogram (lock-exact:
        concurrent observers never lose a count)."""
        with self._lock:
            self._hists[name].observe(value)

    def observe_many(self, name: str, values) -> None:
        """Record a batch of samples into one histogram under a SINGLE
        lock acquisition — for hot loops that produce a vector of
        observations per iteration (e.g. the echo reservoir's per-draw
        sample ages): one lock round trip instead of len(values)."""
        with self._lock:
            h = self._hists[name]
            for v in values:
                h.observe(v)

    @contextlib.contextmanager
    def span(self, name: str):
        annotate = _TraceAnnotation or _bind_trace_annotation()
        annotation = annotate(name) if annotate else None
        if annotation:
            annotation.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if annotation:
                annotation.__exit__(None, None, None)
            with self._lock:
                s = self._spans[name]
                s[0] += 1
                s[1] += dt
                # Spans FEED the histogram of the same name, under the
                # same lock acquisition: histogram counts sum exactly
                # to span counts, by construction, at any concurrency.
                self._hists[name].observe(dt)
                if self._events is not None:
                    self._events.append(
                        (name, t0, dt, threading.get_ident())
                    )

    # -- span events (Chrome-trace source) -----------------------------------

    def enable_span_events(self, capacity: int = 200_000) -> None:
        """Start recording one ``(name, t0, dur_s, tid)`` event per span
        into a bounded ring (oldest dropped past ``capacity``).
        Timestamps are ``perf_counter`` seconds — the same clock the
        span aggregates use, so the exported trace lines up with spans
        taken anywhere in the process."""
        with self._lock:
            self._events = deque(self._events or (), maxlen=int(capacity))

    def disable_span_events(self) -> None:
        with self._lock:
            self._events = None

    def span_events(self) -> list:
        with self._lock:
            return list(self._events or ())

    # -- snapshots ------------------------------------------------------------

    def _spans_locked(self) -> dict:
        out = {}
        for k, (c, t) in self._spans.items():
            d = {
                "count": c,
                "total_s": t,
                "mean_ms": (t / c * 1e3) if c else 0.0,
            }
            h = self._hists.get(k)
            if h is not None and h.count:
                d["p50_ms"] = h.quantile(0.50) * 1e3
                d["p95_ms"] = h.quantile(0.95) * 1e3
                d["p99_ms"] = h.quantile(0.99) * 1e3
            out[k] = d
        return out

    def spans(self) -> dict:
        with self._lock:
            return self._spans_locked()

    def histograms(self) -> dict:
        with self._lock:
            return {k: h.summary() for k, h in self._hists.items()}

    def histogram_buckets(self) -> dict:
        """``name -> (cumulative_buckets, count, sum)`` snapshot — the
        raw-bucket view the Prometheus exporter renders."""
        with self._lock:
            return {
                k: (h.cumulative_buckets(), h.count, h.total)
                for k, h in self._hists.items()
            }

    def report(self, include_buckets: bool = False) -> dict:
        # One lock acquisition for the WHOLE snapshot: counters, gauges,
        # spans, and histograms are mutually consistent (no worker can
        # bump a counter between the copies). ``include_buckets`` adds
        # the raw cumulative-bucket view under the SAME lock, so an
        # exporter can render native histograms from the same snapshot
        # as the counters beside them (a separate histogram_buckets()
        # call races spans recorded in between).
        with self._lock:
            out = {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "spans": self._spans_locked(),
                "histograms": {
                    k: h.summary() for k, h in self._hists.items()
                },
            }
            if include_buckets:
                out["histogram_buckets"] = {
                    k: (h.cumulative_buckets(), h.count, h.total)
                    for k, h in self._hists.items()
                }
            return out

    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self._spans.clear()
            self._hists.clear()
            if self._events is not None:
                self._events.clear()


# Default process-wide registry (imports stay cheap; no jax dependency).
metrics = Metrics()


# jax.profiler supports exactly ONE active trace per process;
# start_trace raises on a second. The SLO watchdog's flight recorder
# may fire a capture at any moment — possibly inside a user's own open
# trace — so activation is tracked under a module lock and a nested
# trace degrades to a logged no-op instead of killing the run.
_trace_lock = threading.Lock()
_trace_active = False


@contextlib.contextmanager
def trace(logdir: str):
    """JAX profiler trace around a code block; view in TensorBoard/XProf.

    Reentrancy-safe: if a trace is already active in this process (the
    profiler allows only one), the nested call logs a warning and runs
    the block untraced instead of raising out of
    ``jax.profiler.start_trace`` — so a watchdog-triggered capture can
    never take down a run that was already being profiled.

    >>> with trace("/tmp/profile"):
    ...     for batch in pipeline: step(state, batch)
    """
    global _trace_active
    import jax

    with _trace_lock:
        already = _trace_active
        if not already:
            _trace_active = True
    if already:
        from blendjax.utils.logging import get_logger

        get_logger("metrics").warning(
            "jax profiler trace already active: nested trace(%r) "
            "degrades to a no-op", logdir,
        )
        yield
        return
    try:
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    finally:
        with _trace_lock:
            _trace_active = False
