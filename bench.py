"""blendjax benchmark: Cube-scene stream + CNN train step, images/sec.

Reproduces the reference benchmark's semantics (``benchmarks/benchmark.py``:
batch 8, 640x480 RGBA cube scene, N producer instances, first batches
excluded as warmup, timing covers render + transfer + decode + batching)
and additionally runs a real train step on the accelerator per batch —
strictly more work per image than the reference measured.

Baseline (BASELINE.md): reference best published aggregate is 0.012
s/image = 83.3 images/s with 4 Blender instances; ``vs_baseline`` is
measured_throughput / 83.3.

The headline metric is the tile-delta stream (the flagship encoding); a
shorter full-frame measurement is embedded as ``detail.raw_row`` so the
non-sparse path is tracked too. It runs the
lossless full-frame palette codec by default (no temporal assumption —
the sparse-free path a skeptic benchmarks; ``blendjax.ops.tiles
.palettize_frames``); set ``BLENDJAX_BENCH_RAW_ENCODING=raw`` for the
uncompressed variant or ``BLENDJAX_BENCH_RAW_ROW=0`` to skip the row.

Prints exactly one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BATCH = 8
SHAPE = (480, 640)
WARMUP_BATCHES = 4
# Workload size / cap are env-tunable so the CI bench-smoke job can run
# the WHOLE harness (producers, pipeline, record assembly) in seconds on
# a CPU runner — the knobs shrink the measurement, never change its
# shape, so the smoke record stays structurally identical to a real one.
MEASURE_ITEMS = int(os.environ.get("BLENDJAX_BENCH_MEASURE_ITEMS", "512"))
BASELINE_IMG_PER_SEC = 1.0 / 0.012  # Readme.md:92, 4 instances
TIME_CAP_S = float(os.environ.get("BLENDJAX_BENCH_TIME_CAP_S", "120"))
ENCODING = os.environ.get("BLENDJAX_BENCH_ENCODING", "tile")
CHUNK = int(os.environ.get("BLENDJAX_BENCH_CHUNK", "16"))
# Decode-then-step is the headline's default; 1 fuses the decode into
# the train jit (one device call per step instead of two). Which is
# faster on the chip is open (ROADMAP S6).
FUSED = os.environ.get("BLENDJAX_BENCH_FUSED", "0") == "1"
RAW_ROW = os.environ.get("BLENDJAX_BENCH_RAW_ROW", "1") == "1"
# StreamFormer-on-the-live-stream row: the train layer's non-toy
# performance evidence. Off only by explicit request.
TRANSFORMER_ROW = (
    os.environ.get("BLENDJAX_BENCH_TRANSFORMER_ROW", "1") == "1"
)
# Dispatch the step from a worker thread, overlapping the dispatch with
# the next group's wait. Off by default.
OVERLAP = os.environ.get("BLENDJAX_BENCH_OVERLAP", "0") == "1"
# Ingest worker pool A/B row (docs/performance.md "choosing
# ingest_workers"): measures the tile stream at ingest_workers=1 vs 2 so
# the sharded recv/decode pool's win (or non-win, on 1-core hosts) is
# re-evidenced every round.
INGEST_AB = os.environ.get("BLENDJAX_BENCH_INGEST_AB", "1") == "1"
# Async overlap driver A/B row (docs/performance.md "Closing the
# live-MFU gap"): the fused single-dispatch-per-step path driven by
# TrainDriver at inflight=1 (serialized baseline) vs inflight=N, with
# dispatch counts, decode.dispatch elimination, and the steps-in-flight
# high-water mark in the record.
LIVE_OVERLAP = os.environ.get("BLENDJAX_BENCH_LIVE_OVERLAP", "1") == "1"
LIVE_OVERLAP_INFLIGHT = int(
    os.environ.get("BLENDJAX_BENCH_LIVE_OVERLAP_INFLIGHT", "4")
)
# Distributed frame tracing (blendjax.obs.trace): producers stamp every
# Nth message with a `_trace` context the consumer stages append to;
# driver rows complete the records at step retirement and report them
# under stages["trace"]. Smaller than the library default (64) because
# a bench window is short; bench-smoke shrinks it further so at least
# one sampled frame completes end-to-end inside the tiny CI window
# (CI-asserted). 0 disables stamping.
TRACE_EVERY = int(os.environ.get("BLENDJAX_BENCH_TRACE_EVERY", "8"))
# Optional Chrome-trace export of the completed frame traces (flow
# arrows producer lane -> consumer lanes): written after each driver
# row that completed records, so the file holds the LAST such row's
# window (the artifact bench-smoke uploads).
TRACE_EXPORT = os.environ.get("BLENDJAX_BENCH_TRACE_EXPORT", "")
# Data-echoing A/B row (docs/performance.md "Echoing past a
# producer-bound pipeline"): echo off vs max_echo_factor in {4, 16} on
# the live stream — live img/s INTO the step, unique fraction, final
# loss, and the exact echo accounting + one-dispatch-per-step contract
# (both CI-asserted in bench-smoke).
LIVE_ECHO = os.environ.get("BLENDJAX_BENCH_LIVE_ECHO", "1") == "1"
LIVE_ECHO_FACTORS = tuple(
    int(v) for v in os.environ.get(
        "BLENDJAX_BENCH_LIVE_ECHO_FACTORS", "4,16"
    ).split(",") if v
)
# Elastic producer-fleet A/B row (docs/fleet.md): a fixed fleet of 2
# rate-capped synthetic producers vs an autoscaled fleet the
# FleetController grows on live stall-doctor verdicts. Pure CPU (the
# synthetic tier needs no Blender and no device step), so the row runs
# identically on CI; it records the instance-count trajectory, the
# scale-event log, the verdict sequence, and the two CI contracts
# (at least one scale-up fired; wire.seq_gaps == 0 across every
# membership change). FLEET_RATE caps each instance's frames/s so one
# instance is a known supply increment and producer-bound is
# reproducible on any host.
LIVE_FLEET = os.environ.get("BLENDJAX_BENCH_LIVE_FLEET", "1") == "1"
FLEET_RATE = float(os.environ.get("BLENDJAX_BENCH_FLEET_RATE", "40"))
FLEET_MAX = int(os.environ.get("BLENDJAX_BENCH_FLEET_MAX", "4"))
# Wire-decode A/B row (docs/performance.md "Closing the live-MFU
# gap"): zlib "ndz" (host inflate, decode-ahead pool) vs run-length
# "ndr" (expansion deferred INTO the fused train dispatch) on the
# synthetic tier, both through the driver-placed one-dispatch path,
# against a step-alone probe of the SAME fused step — so the
# live-to-step-alone settled-rate ratio isolates wire + host decode +
# placement overhead. CI asserts the ratio floor, dispatch_per_step ==
# 1.0 with ZERO standalone decode dispatches on the ndr leg,
# seq_gaps == 0, and f32 loss equality between ndr-decoded and
# nd-decoded runs of the same recorded stream.
LIVE_WIRE = os.environ.get("BLENDJAX_BENCH_LIVE_WIRE", "1") == "1"
WIRE_TIME_CAP_S = float(
    os.environ.get("BLENDJAX_BENCH_WIRE_TIME_CAP_S", "14")
)
WIRE_RATE = float(os.environ.get("BLENDJAX_BENCH_WIRE_RATE", "300"))
# Conservative: on a 1-core dev box the measured ratio is ~1.0 (the
# live path matches the fused step-alone rate); the floor guards
# against the input-bound regime regressing, not for headroom.
WIRE_RATIO_FLOOR = float(
    os.environ.get("BLENDJAX_BENCH_WIRE_RATIO_FLOOR", "0.25")
)
# Closed-loop scenario A/B row (docs/scenarios.md): the SAME 2-producer
# synthetic fleet rendering a 2-scenario space (one with irreducible
# label noise — the high-loss scenario) through the fused echo path,
# once with a FROZEN uniform mixture and once with the adaptive
# curriculum republishing the space on a cadence. CI asserts the
# structural contracts: per-scenario fresh+echoed sums EXACTLY to
# steps*batch, >= 2 distinct scenario ids observed, the curriculum leg
# advanced the space version >= 2 and shifted mixture weight toward the
# high-loss scenario, seq_gaps == 0, dispatch_per_step == 1.0.
LIVE_SCENARIO = os.environ.get("BLENDJAX_BENCH_LIVE_SCENARIO", "1") == "1"
SCENARIO_TIME_CAP_S = float(
    os.environ.get("BLENDJAX_BENCH_SCENARIO_TIME_CAP_S", "20")
)
SCENARIO_MIN_STEPS = int(
    os.environ.get("BLENDJAX_BENCH_SCENARIO_MIN_STEPS", "40")
)
# Live kill-9/resume row (docs/checkpointing.md): a child process
# trains a deterministic stream over a REAL publisher socket with
# async checkpointing enabled; the parent SIGKILLs it after the first
# COMMITTED snapshot, resumes in a fresh child (train state + session:
# driver counters, lineage seq positions), and compares the full f32
# loss vector against an uninterrupted run — the PR 8 equality trick
# applied to time. CI asserts: trajectories identical, seq_gaps == 0
# across the restart (the resumed publisher's fresh numbering reads as
# a RESTART through the restored lineage, never a gap storm), and
# dispatch_per_step == 1.0 with checkpointing enabled (ckpt.save_ms
# lives on the writer thread, never inside a step dispatch). Pure
# CPU/loopback. On failure the snapshot dirs are
# kept (BLENDJAX_BENCH_RESUME_DIR) for artifact upload.
LIVE_RESUME = os.environ.get("BLENDJAX_BENCH_LIVE_RESUME", "1") == "1"
RESUME_STEPS = int(os.environ.get("BLENDJAX_BENCH_RESUME_STEPS", "16"))
RESUME_DIR = os.environ.get("BLENDJAX_BENCH_RESUME_DIR", "")
# Instant-start row (docs/performance.md "Instant start"): three fresh
# child processes over loopback. Legs 1+2 run the ndz wire sharing one
# persistent compilation cache dir — leg 1 is the cold trace+compile,
# leg 2 must come up warm (manifest all hits, compile_ms strictly below
# cold by the CI-pinned ratio). Leg 3 runs the SAME deterministic
# stream through the shared-memory ring (zero-copy local transport):
# CI asserts its f32 loss vector identical to the ndz leg's, zero seq
# gaps and zero torn slots on the clean run, one dispatch per step,
# and shm throughput at least matching the compressed wire. Pure
# CPU/loopback.
LIVE_START = os.environ.get("BLENDJAX_BENCH_LIVE_START", "1") == "1"
START_STEPS = int(os.environ.get("BLENDJAX_BENCH_START_STEPS", "12"))
# RL actor-learner row (docs/rl.md): cartpole trained END TO END by
# blendjax.rl — remote producer envs under an ActorPool, a
# TrajectoryReservoir, and the one-dispatch DQN learner — as a
# uniform-vs-prioritized A/B, plus an 8-device CPU-mesh leg
# (subprocess, like multichip_live) and a kill -9 -> resume leg
# through the session store. Pure CPU/loopback.
# CI asserts dispatch_per_step == 1.0 on the learner path, the
# donation audit (ring + priorities + params updated in place), exact
# transition accounting, and the episode-return sanity floor.
LIVE_RL = os.environ.get("BLENDJAX_BENCH_LIVE_RL", "1") == "1"
RL_STEPS = int(os.environ.get("BLENDJAX_BENCH_RL_STEPS", "300"))
RL_MESH_STEPS = int(os.environ.get("BLENDJAX_BENCH_RL_MESH_STEPS", "80"))
RL_ENVS = int(os.environ.get("BLENDJAX_BENCH_RL_ENVS", "2"))
# the reward-SANITY floor (ROADMAP item 1): well below a healthy
# random-policy baseline (~40 on this cartpole), far above the ~1-3 a
# miswired env/reward/done path produces — the row proves the loop
# trains, the curve ships in the record for the real claim
RL_RETURN_FLOOR = float(
    os.environ.get("BLENDJAX_BENCH_RL_RETURN_FLOOR", "15")
)
RL_DIR = os.environ.get("BLENDJAX_BENCH_RL_DIR", "")
# Multi-chip live row (docs/performance.md "Going multi-chip"): the
# SAME live pipeline (synthetic producers -> ShardedHostIngest ->
# DeviceFeeder -> MeshTrainDriver) at mesh sizes 1/2/4/8 with a FIXED
# per-chip batch (weak scaling — the regime real DP runs in), on a
# forced 8-device CPU mesh in a SUBPROCESS (the device count must be
# set before the backend initializes, which this process already did).
# Reports img/s per mesh size, the 8-vs-1 speedup, and
# scaling_efficiency = speedup / 8; CI asserts the structural
# contracts (dispatch_per_step == 1.0, seq_gaps == 0, efficiency
# reported). Pure CPU.
MULTICHIP_LIVE = os.environ.get("BLENDJAX_BENCH_MULTICHIP", "1") == "1"
MULTICHIP_MESHES = tuple(
    int(v) for v in os.environ.get(
        "BLENDJAX_BENCH_MULTICHIP_MESHES", "1,2,4,8"
    ).split(",") if v
)
MULTICHIP_TIME_CAP_S = float(
    os.environ.get("BLENDJAX_BENCH_MULTICHIP_TIME_CAP_S", "5")
)
# Interleaved passes, best-of per leg (like BLENDJAX_BENCH_PASSES for
# the headline): on shared-core hosts a single 5s run swings 2x, and
# interleaving keeps one noisy stretch from biasing one mesh size.
MULTICHIP_PASSES = int(
    os.environ.get("BLENDJAX_BENCH_MULTICHIP_PASSES", "2")
)
# Device-ledger row (docs/performance.md "Reading the device ledger"):
# the blendjax.obs.devledger contracts exercised live. Single-chip leg:
# TrainDriver.build on synthetic in-memory batches — cost-model MFU
# (ledger-derived flops_per_image) within 10% of the hand-fed
# measure_model_flops probe on the SAME program, collective_bytes == 0,
# device.retraces == 0 on the bucketed dispatch path and EXACTLY 1
# (signature attributed) after a deliberately unbucketed shape is
# injected. Mesh leg (subprocess, forced 8-device CPU mesh like
# multichip_live): the data-parallel grad sync's all-reduce bytes must
# match the analytic expectation (param bytes x policy dtype width).
# Pure CPU; all four contracts CI-asserted.
LIVE_DEVLEDGER = (
    os.environ.get("BLENDJAX_BENCH_LIVE_DEVLEDGER", "1") == "1"
)
# When set, the full ledger report (per-signature entries + retrace
# events) is written to this path beside the record — the
# device_ledger.json artifact bench-smoke uploads.
DEVLEDGER_EXPORT = os.environ.get("BLENDJAX_BENCH_DEVLEDGER_EXPORT", "")
# Model-parallel A/B row (docs/parallelism.md "Choosing a layout"):
# the SAME model + deterministic f32 batch stream trained end-to-end
# under each mesh layout on a forced 8-device CPU mesh (subprocess,
# same dance as multichip_live), diffing throughput and the ledger's
# per-axis collective bytes. Contracts CI asserts: final f32 loss
# equal across every layout (the layouts are mathematically the same
# program), dispatch_per_step == 1.0 on every leg, the pure-data leg
# all-reduce-only, fsdp-axis bytes nonzero exactly on fsdp layouts
# (param all-gather-on-use + grad sync), tp-axis bytes nonzero on tp
# layouts, and the forced-HBM-budget leg: the replicated layout's
# device.hbm_peak figure EXCEEDS the budget while data×fsdp fits and
# still trains. Per-axis attribution is by replica-group size, so
# contracts are only asserted on size-unambiguous layouts (the 2×2×2
# leg reports bytes but is flagged attribution_ambiguous).
MODEL_PARALLEL_AB = (
    os.environ.get("BLENDJAX_BENCH_MODEL_PARALLEL", "1") == "1"
)
MODEL_PARALLEL_LAYOUTS = tuple(
    v for v in os.environ.get(
        "BLENDJAX_BENCH_MODEL_PARALLEL_LAYOUTS",
        "data8,data2xfsdp4,data4xtp2,data2xfsdp2xtp2",
    ).split(",") if v
)
MODEL_PARALLEL_STEPS = int(
    os.environ.get("BLENDJAX_BENCH_MODEL_PARALLEL_STEPS", "6")
)
# f32 cross-layout loss tolerance: resharding reorders f32 reductions
# (all-gather boundaries move), so "equal" means equal to reduction
# rounding — 5e-5 is ~10x the observed drift, far below any real
# divergence (a wrong program differs in the first decimal).
MODEL_PARALLEL_LOSS_TOL = float(
    os.environ.get("BLENDJAX_BENCH_MODEL_PARALLEL_LOSS_TOL", "5e-5")
)
# Forced per-device HBM budget (bytes) for the does-not-fit contract;
# "auto" pins it to the midpoint of the replicated and fsdp legs'
# measured device.hbm_peak figures, so the contract stays meaningful
# as the bench model changes size.
MODEL_PARALLEL_HBM_BUDGET = os.environ.get(
    "BLENDJAX_BENCH_MODEL_PARALLEL_HBM_BUDGET", "auto"
)
# Precision-policy A/B row (docs/performance.md "Raising the device
# ceiling"): step-alone img/s + mfu_step_alone for the bf16-grads vs
# bf16-compute policies, on BOTH the headline CNN and the longseq
# transformer. On TPU it runs the real bench geometries; elsewhere a
# shrunken geometry keeps the row (and its CI structural assertions)
# cheap — the numbers are only meaningful on the real chip, the
# structure is asserted everywhere.
PRECISION_AB = os.environ.get("BLENDJAX_BENCH_PRECISION_AB", "1") == "1"
# The non-sparse row's codec: 'pal' (lossless full-frame palette; 4-8x
# fewer bytes across socket AND host->device, decoded by a device
# gather) or 'raw' (uncompressed frames). pal chunk-groups 8 batches
# per transfer+scan.
RAW_ENCODING = os.environ.get("BLENDJAX_BENCH_RAW_ENCODING", "pal")
RAW_CHUNK = int(os.environ.get("BLENDJAX_BENCH_RAW_CHUNK", "8"))
# Tile geometry: "16x32" (default) = rectangular tiles whose rows span
# 128 lanes at C=4, so the consumer decode takes the direct-spatial
# Pallas kernel (one pass: no slot buffer, no ref-broadcast init, no
# transpose); "16" = square 16x16 (slot-scatter decode). chip_smoke.py
# checks both kernels bit-exact on the chip; which geometry is faster
# end to end there is not measured.
TILE_GEOM = os.environ.get("BLENDJAX_BENCH_TILE", "16x32")
_TILE_ARGS = TILE_GEOM.split("x")


def tile_capacity_default(th: int, tw: int) -> str:
    """Default ``--tile-capacity`` for the cube scene at 480x640.

    The two benchmarked geometries get their measured max changed-tile
    counts, 32-aligned (282 @16x16 -> 288; 154 @16x32 -> 160). Any other
    geometry gets an estimate scaled from the 16x16 measurement by tile
    area with a boundary margin, clamped to the grid size: oversizing
    only pads the wire, while undersizing costs a mid-run capacity
    growth + decode recompile. Shared with the A/B script so both always
    benchmark the capacity the bench would use."""
    measured = {(16, 16): 288, (16, 32): 160}
    if (th, tw) in measured:
        return str(measured[(th, tw)])
    import math

    grid = math.ceil(SHAPE[0] / th) * math.ceil(SHAPE[1] / tw)
    changed_px = 282 * 256  # the 16x16 measurement, in pixels
    est = math.ceil(changed_px / (th * tw) * 1.3 / 32) * 32
    return str(max(1, min(est, grid)))  # grid can be < 32 for huge tiles


TILE_CAPACITY = os.environ.get(
    "BLENDJAX_BENCH_TILE_CAPACITY",
    tile_capacity_default(int(_TILE_ARGS[0]), int(_TILE_ARGS[-1])),
)
# Narrowest palette index width the cube producers ship. Three faces and
# the background are 4 colors (2 bits), but about one frame in 200 holds
# a fifth, and a batch with a wider index is another wire shape: it
# breaks the consumer's chunk group (groups of 9, 1, 14, 1, ... instead
# of 16) and each distinct (group length, width) compiles its own step
# (PR 21's first chip run: four more fused-step compiles inside eight
# driver steps). 4 bits keeps every batch one shape at twice the
# tile-payload bytes.
TILE_PAL_BITS = "4"

def measure(encoding: str, chunk: int, items: int, time_cap: float,
            with_stages: bool = True, tile_args=None,
            tile_capacity=None, model=None, loss_fn=None,
            ingest_workers: int = 1,
            driver_inflight: int | None = None,
            driver_sync_every: int = 16) -> dict:
    """One full producer-fleet + pipeline + train measurement pass.

    ``tile_args``/``tile_capacity`` default to the module-level bench
    configuration; A/B scripts pass explicit values instead of mutating
    module globals (ADVICE r4). ``model``/``loss_fn`` default to the
    headline CubeRegressor with the corner loss; the transformer row
    passes a StreamFormer + reshaping loss instead. ``ingest_workers``
    feeds straight through to ``StreamDataPipeline`` (>=2 shards the
    consumer's receive/decode across threads; the per-shard
    ``ingest.recv.shard*`` spans land in the stage breakdown).
    ``driver_inflight`` switches the consumer loop to the async overlap
    path: ``emit_packed=True`` + ``make_fused_tile_step`` (exactly one
    device dispatch per step, no standalone decode.dispatch) driven by
    ``TrainDriver(inflight=N, sync_every=driver_sync_every)``; the
    driver's stats land under ``result["driver"]``."""
    import jax

    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.models import CubeRegressor
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import (
        TrainDriver,
        make_chunked_supervised_step,
        make_fused_tile_step,
        make_supervised_step,
        make_train_state,
    )
    from blendjax.obs import diagnose
    from blendjax.obs.lineage import lineage
    from blendjax.obs.trace import tracer
    from blendjax.utils.metrics import metrics as reg

    tile_args = (
        list(_TILE_ARGS) if tile_args is None
        else [str(a) for a in tile_args]  # subprocess argv must be str
    )
    tile_capacity = (
        TILE_CAPACITY if tile_capacity is None else str(tile_capacity)
    )
    cpu = os.cpu_count() or 1
    # Single-core hosts still run TWO producers: each spends a sizable
    # slice blocked on socket IO/HWM, and a second instance fills those
    # gaps.
    instances = max(1, min(6, cpu - 1)) if cpu > 1 else 2
    instances = int(os.environ.get("BLENDJAX_BENCH_INSTANCES", instances))
    mesh = create_mesh({"data": -1})
    sharding = batch_sharding(mesh)

    model = CubeRegressor() if model is None else model
    state = make_train_state(
        model, np.zeros((BATCH, *SHAPE, 4), np.uint8), mesh=mesh
    )
    # One jitted scan of `chunk` sequential updates per device call: same
    # SGD trajectory as per-batch stepping, 1/chunk the transfers and
    # device calls.
    # Tile and pal streams both chunk-group; raw mode steps per batch.
    chunk = chunk if encoding in ("tile", "pal") else 1
    driver = None
    if driver_inflight is not None:
        # Async overlap path: fused decode+step (one dispatch per step)
        # with up to `inflight` dispatches outstanding. inflight=1 is
        # the serialized A/B baseline on the identical program. On v5e
        # the driver also maintains the live train.mfu gauge (the
        # always-on version of this file's bench-time MFU rows).
        fpi = _live_flops_per_image(model, loss_fn)
        step = make_fused_tile_step(loss_fn=loss_fn)
        driver = TrainDriver(
            step, state, inflight=driver_inflight,
            sync_every=driver_sync_every,
            flops_per_image=fpi,
            peak_flops=V5E_PEAK_FLOPS if fpi else None,
        )
    elif chunk > 1 and FUSED:
        step = make_fused_tile_step(loss_fn=loss_fn)
    elif chunk > 1:
        step = make_chunked_supervised_step(loss_fn=loss_fn)
    else:
        step = make_supervised_step(
            mesh=mesh, batch_sharding=sharding, loss_fn=loss_fn
        )

    producer = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "datagen", "cube_producer.py",
    )
    with PythonProducerLauncher(
        script=producer,
        num_instances=instances,
        named_sockets=["DATA"],
        seed=0,
        proto="ipc",  # same-host fleet: unix sockets beat TCP loopback
        # Producers render into (BATCH, H, W, 4) buffers and publish one
        # message per batch. With tile-delta encoding (default) only the
        # 16x16 tiles the cube touches cross the wire and the host->device
        # copy; the consumer reconstructs bit-exact full frames on device
        # (blendjax.ops.tiles).
        # --tile-rgba: full-channel tiles decode through the Pallas
        # scatter kernel (~25x faster than the XLA scatter on TPU); the
        # ~33% extra wire bytes are the cheaper side of that trade.
        # --tile-capacity pins one wire shape across the fleet: one
        # consumer decode compilation, unbroken chunk groups (the cube
        # touches a constant 276 of 1200 tiles at this size, so 288 is
        # the tightest 32-aligned fit; the sticky capacity still grows
        # on overflow). --tile-pal-bits does the same for the palette
        # index width (TILE_PAL_BITS).
        instance_args=[
            ["--shape", str(SHAPE[0]), str(SHAPE[1]), "--batch", str(BATCH),
             "--encoding", encoding, "--tile", *tile_args, "--tile-rgba",
             "--tile-capacity", tile_capacity,
             "--tile-pal-bits", TILE_PAL_BITS,
             "--trace-every", str(TRACE_EVERY)]
        ] * instances,
    ) as launcher:
        def batch_images(sb):
            if "_packed" in sb:
                from blendjax.ops.tiles import TILEIDX_SUFFIX

                # packed chunk group: K' rows x the per-batch lead dim B
                # (the tileidx lead for tile groups, xy for pal groups)
                lead = next(
                    (s[0] for n, d, s, o, b in sb["_spec"]
                     if n.endswith(TILEIDX_SUFFIX)),
                    None,
                )
                if lead is None:
                    lead = next(
                        s[0] for n, d, s, o, b in sb["_spec"] if n == "xy"
                    )
                return sb["_packed"].shape[0] * lead
            # chunked superbatches are (K, B, ...); raw batches (B, ...)
            return (
                sb["image"].shape[0] * sb["image"].shape[1]
                if chunk > 1 else sb["image"].shape[0]
            )

        def last_loss(metrics):
            loss = metrics["loss"]
            return float(loss[-1] if getattr(loss, "ndim", 0) else loss)

        def run_step(state, sb):
            if "_packed" in sb:
                return step(state, sb)
            fields = {"image": sb["image"], "xy": sb["xy"]}
            if "_mask" in sb:  # bucket-padded tail: loss-masked rows
                fields["_mask"] = sb["_mask"]
            return step(state, fields)

        with StreamDataPipeline(
            launcher.addresses["DATA"],
            batch_size=BATCH,
            sharding=sharding,
            chunk=chunk,
            emit_packed=(chunk > 1 and FUSED) or driver is not None,
            ingest_workers=ingest_workers,
            timeoutms=60_000,
        ) as pipe:
            it = iter(pipe)
            # >=2 warm calls: the step compiles twice (the second
            # executable specializes to the donated-output layouts the
            # first one produced), and at large chunk a count-based
            # warmup would leave that second compile inside the
            # measured window.
            for _ in range(max(2, WARMUP_BATCHES // chunk)):
                sb = next(it)  # warmup: compile + fill queues
                if driver is not None:
                    driver.submit(sb)
                else:
                    state, metrics = run_step(state, sb)
            # Sync by fetching the loss value: it transitively depends
            # on every dispatched step (donated-state chain).
            if driver is not None:
                driver.drain()
            else:
                last_loss(metrics)

            reg.reset()  # stage spans cover the measured window only
            lineage.reset()  # staleness/gap lineage too (same window)
            tracer.reset()  # completed frame traces too (same window)
            drv0 = dict(driver.stats) if driver is not None else None
            images = 0
            t_next = t_step = 0.0
            pool = fut = None
            if OVERLAP:
                # Dispatch step k from a worker thread while the main
                # thread waits on group k+1. The state dependency is
                # preserved: the next step's submit happens only after
                # the previous result().
                from concurrent.futures import ThreadPoolExecutor

                pool = ThreadPoolExecutor(1)

            # ONE measured loop for both modes (the two must stay
            # strictly comparable); only the dispatch differs.
            t0 = time.perf_counter()
            while images < items:
                ta = time.perf_counter()
                sb = next(it)
                tb = time.perf_counter()
                if driver is not None:
                    driver.submit(sb)
                elif pool is not None:
                    if fut is not None:
                        state, metrics = fut.result()
                    fut = pool.submit(run_step, state, sb)
                else:
                    state, metrics = run_step(state, sb)
                tc = time.perf_counter()
                t_next += tb - ta
                t_step += tc - tb
                images += batch_images(sb)
                if tc - t0 > time_cap:
                    break
            if fut is not None:
                state, metrics = fut.result()
            if pool is not None:
                pool.shutdown(wait=True)
            t_sync0 = time.perf_counter()
            if driver is not None:
                final_loss = driver.drain()  # full drain, see above
            else:
                final_loss = last_loss(metrics)  # full drain, see above
            t_sync = time.perf_counter() - t_sync0
            dt = time.perf_counter() - t0

    result = {
        "value": round(images / dt, 2),
        "instances": instances,
        "encoding": encoding,
        "chunk": chunk,
        "batch": BATCH,
        "images": images,
        "seconds": round(dt, 2),
        "final_loss": final_loss,
    }
    if driver is not None:
        # measured-window driver behavior only (warmup deltas removed;
        # the high-water mark is a max, not a delta, and warmup cannot
        # exceed the same `inflight` bound)
        stats = driver.stats
        result["driver"] = {
            "inflight": stats["inflight"],
            "sync_every": driver_sync_every,
            "dispatches": stats["dispatches"] - drv0["dispatches"],
            "steps": stats["steps"] - drv0["steps"],
            "host_blocks": stats["host_blocks"] - drv0["host_blocks"],
            "syncs": stats["syncs"] - drv0["syncs"],
            "inflight_hwm": stats["inflight_hwm"],
        }
    if with_stages:
        # Per-stage breakdown: consumer-loop wall
        # split + pipeline spans, so the binding constraint is
        # driver-evidenced. `consumer_wall` buckets are disjoint and sum
        # to ~dt; span totals overlap them (spans run inside next())
        # except ingest.recv, which runs in the ingest thread
        # concurrently with the main loop. Since PR 4 every span also
        # carries exact-count log-bucketed percentiles (mean hides the
        # tail), the per-producer lineage block records e2e staleness +
        # drop/reorder accounting, and the stall doctor's one-line
        # verdict names the bound instead of leaving it to the reader.
        report = reg.report()
        lineage_report = lineage.report()
        verdict = diagnose(
            report,
            driver=result.get("driver"),
            lineage=lineage_report,
        )
        result["stages"] = {
            "consumer_wall": {
                "next_batch_s": round(t_next, 3),
                "step_dispatch_s": round(t_step, 3),
                "final_sync_s": round(t_sync, 3),
            },
            "spans": {
                k: {
                    "count": v["count"],
                    "total_s": round(v["total_s"], 3),
                    "mean_ms": round(v["mean_ms"], 3),
                    "p50_ms": round(v.get("p50_ms", v["mean_ms"]), 3),
                    "p95_ms": round(v.get("p95_ms", v["mean_ms"]), 3),
                    "p99_ms": round(v.get("p99_ms", v["mean_ms"]), 3),
                }
                for k, v in report["spans"].items()
            },
            "counters": {
                k: int(v) for k, v in report["counters"].items()
                if k.startswith(
                    ("tiles.", "ingest.", "pal.", "wire.", "train.",
                     "feed.", "echo.", "device.")
                )
            },
            # Occupancy gauges beside the counters: queue_full_waits
            # alone can't separate backpressure (queue_depth_hwm pinned
            # at prefetch) from overlap stalls (hwm ~0 while the
            # consumer starves) — the gauge pair makes the two regimes
            # distinguishable in the record.
            "gauges": {
                k: v for k, v in report["gauges"].items()
                if k.startswith(
                    ("ingest.", "feed.", "train.", "echo.", "device.")
                )
            },
            # Observe-only histograms (spans already carry their own
            # percentiles above): the driver's device-timeline step
            # histogram, trace transitions, staleness, echo ages.
            "histograms": {
                k: {
                    "count": v["count"],
                    "p50": round(v["p50"], 4),
                    "p95": round(v["p95"], 4),
                    "p99": round(v["p99"], 4),
                    "max": round(v["max"], 4),
                }
                for k, v in report["histograms"].items()
                if k.startswith(("train.", "trace.", "wire.", "echo."))
            },
            # Per-producer frame lineage: e2e staleness percentiles,
            # exact seq gap/reorder counts, latest piggybacked producer
            # telemetry (render span, publish rate) — the fleet view.
            "lineage": lineage_report,
            "doctor": verdict.render(),
            # Distributed frame traces completed inside the measured
            # window (driver rows only — completion happens at step
            # retirement): per-transition percentiles, end-to-end stage
            # completeness, mono ordering. Non-driver rows report
            # completed == 0 (their sampled contexts never reach a
            # terminal stage).
            "trace": tracer.report(),
        }
        if TRACE_EXPORT and tracer.records():
            from blendjax.obs.exporters import write_chrome_trace

            write_chrome_trace(TRACE_EXPORT)
    return result


def measure_step_alone(chunk: int, calls: int = 8, model=None,
                       loss_fn=None, shape=None, batch=None,
                       precision=None) -> dict:
    """Chip-side ceiling: the chunked train step on an already-on-device
    superbatch, no pipeline — the denominator of the utilization figure
    (achieved img/s / step-alone img/s).
    ``shape``/``batch`` default to the bench frame geometry; the
    long-sequence transformer sub-row passes larger frames.
    ``precision`` names a :mod:`blendjax.train.precision` policy for
    the step builders (the precision A/B row passes it; ``None`` keeps
    the default ``bf16-compute`` discipline)."""
    import jax

    from blendjax.models import CubeRegressor
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import (
        make_chunked_supervised_step,
        make_supervised_step,
        make_train_state,
    )

    shape = SHAPE if shape is None else shape
    batch = BATCH if batch is None else batch
    mesh = create_mesh({"data": -1})
    sharding = batch_sharding(mesh)
    rng = np.random.default_rng(0)
    # Same mesh/sharding setup AND step builder as measure(): the
    # utilization ratio must compare identical programs.
    state = make_train_state(
        CubeRegressor() if model is None else model,
        np.zeros((batch, *shape, 4), np.uint8), mesh=mesh,
    )
    if chunk > 1:
        step = make_chunked_supervised_step(
            loss_fn=loss_fn, precision=precision
        )
        lead = (chunk, batch)
    else:
        step = make_supervised_step(
            mesh=mesh, batch_sharding=sharding, loss_fn=loss_fn,
            precision=precision,
        )
        lead = (batch,)
    # Chunked fields carry the chunk axis replicated; per-batch fields
    # take the batch sharding directly — matching what the pipeline
    # feeds measure() (layouts ride the arrays; the step jit infers).
    if chunk > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(
            sharding.mesh, PartitionSpec(None, *sharding.spec)
        )
    sb = {
        "image": jax.device_put(
            rng.integers(0, 255, (*lead, *shape, 4), np.uint8), sharding
        ),
        "xy": jax.device_put(
            (rng.random((*lead, 8, 2)) * 64).astype(np.float32), sharding
        ),
    }
    state, m = step(state, sb)  # compile + warm
    float(np.asarray(m["loss"]).reshape(-1)[-1])
    calls = calls if chunk > 1 else calls * 8  # comparable image counts
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(calls):
            state, m = step(state, sb)
        float(np.asarray(m["loss"]).reshape(-1)[-1])  # honest d2h sync
        dt = time.perf_counter() - t0
        best = max(best, calls * chunk * batch / dt)
    return {"img_s": round(best, 1), "chunk": chunk, "calls": calls}


def measure_pipelined_ceiling(chunk: int, items: int = 512,
                              time_cap: float = 60.0) -> dict:
    """Runtime ceiling of the live tile path: pre-stage every wire
    message on the HOST, then replay them through the IDENTICAL
    production pipeline (pack -> placement ring -> decode jit -> chunked
    step). Ingest cost drops to ~zero, so the measured wall is the
    transfer+decode+train pipeline alone — the number the live headline
    could reach if producer supply and ingest were free.
    """
    import jax

    from blendjax.data import StreamDataPipeline
    from blendjax.data.stream import RemoteStream
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.models import CubeRegressor
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import (
        make_chunked_supervised_step,
        make_train_state,
    )

    producer = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "datagen", "cube_producer.py",
    )
    # Capture enough real wire messages for warmup + the measured window
    # (one producer => FIFO => the ref arrives first).
    n_batches = (max(2, WARMUP_BATCHES // chunk) + 1) * chunk + items // BATCH
    captured = []
    with PythonProducerLauncher(
        script=producer, num_instances=1, named_sockets=["DATA"], seed=0,
        proto="ipc",
        instance_args=[
            ["--shape", str(SHAPE[0]), str(SHAPE[1]), "--batch", str(BATCH),
             "--encoding", "tile", "--tile", *_TILE_ARGS, "--tile-rgba",
             "--tile-capacity", TILE_CAPACITY,
             "--tile-pal-bits", TILE_PAL_BITS]
        ],
    ) as launcher:
        stream = RemoteStream(
            launcher.addresses["DATA"], timeoutms=60_000, copy_arrays=True
        )
        it = iter(stream)
        while len(captured) < n_batches:
            captured.append(next(it))
        it.close()  # generator finally: releases the PULL socket

    mesh = create_mesh({"data": -1})
    sharding = batch_sharding(mesh)
    state = make_train_state(
        CubeRegressor(), np.zeros((BATCH, *SHAPE, 4), np.uint8), mesh=mesh
    )
    # Same chunk branching as measure()/measure_step_alone: the ceiling
    # must run the identical step program as the live pass it gates.
    if chunk > 1:
        step = make_chunked_supervised_step()
    else:
        from blendjax.train import make_supervised_step

        step = make_supervised_step(mesh=mesh, batch_sharding=sharding)

    def n_images(sb):
        return (
            sb["image"].shape[0] * sb["image"].shape[1]
            if chunk > 1 else sb["image"].shape[0]
        )

    def replay():
        # Shallow copies: the pipeline's stages pop keys destructively.
        for m in captured:
            yield dict(m)

    def one_pass(warm: bool):
        with StreamDataPipeline(
            replay(), batch_size=BATCH, sharding=sharding, chunk=chunk,
        ) as pipe:
            nonlocal state
            it = iter(pipe)
            if warm:
                for _ in range(max(2, WARMUP_BATCHES // chunk)):
                    sb = next(it)
                    state, metrics_ = step(
                        state, {"image": sb["image"], "xy": sb["xy"]}
                    )
                float(np.asarray(metrics_["loss"]).reshape(-1)[-1])
            images = 0
            t0 = time.perf_counter()
            while images < items:
                sb = next(it)
                state, metrics_ = step(
                    state, {"image": sb["image"], "xy": sb["xy"]}
                )
                images += n_images(sb)
                # report what was measured instead of grinding a slow
                # run far past the cap
                if time.perf_counter() - t0 > time_cap:
                    break
            float(np.asarray(metrics_["loss"]).reshape(-1)[-1])  # drain
            return images, time.perf_counter() - t0

    # Best of 2 measured passes over the same captured messages (the
    # headline it is compared with is itself best-of-N). The second
    # pass is skipped when the first already blew the cap.
    images, dt = one_pass(warm=True)
    if dt <= time_cap:
        i2, d2 = one_pass(warm=False)
        if i2 / d2 > images / dt:
            images, dt = i2, d2
    out = {
        "img_s": round(images / dt, 1),
        "chunk": chunk,
        "images": images,
        "seconds": round(dt, 2),
    }
    if images < items:
        # single truncated sample (second pass skipped): flag it so a
        # depressed ceiling — and any utilization_vs_ceiling > 1 built
        # on it — is not read as live beating the ceiling
        out["capped"] = True
    return out


# The cost-model FLOPs probe lives in the device ledger now
# (blendjax.obs.devledger — one home for the path; the drivers derive
# live MFU numerators from the same cost_analysis() figures). Bench
# imports it back; memoization is keyed by model class + geometry
# inside the ledger module, so the per-class one-extra-lowering cost
# is unchanged. Import-cheap: devledger pulls no jax at module level.
from blendjax.obs.devledger import (  # noqa: E402
    V5E_PEAK_FLOPS,
    measure_model_flops,
)


def _live_flops_per_image(model, loss_fn) -> float | None:
    """``flops_per_image`` for a live driver's ``train.mfu`` gauge;
    None off-v5e (the gauge's peak denominator is chip-specific)."""
    if not _is_v5e():
        return None
    return measure_model_flops(
        model=model, loss_fn=loss_fn, label=type(model).__name__
    )["flops_per_image"]


def _is_v5e() -> bool:
    """MFU against the v5e peak is only meaningful on that chip — a CPU
    fallback (or a different TPU generation, whose peak differs) must
    not print a v5e utilization figure. One definition for every MFU
    site."""
    import jax

    device_kind = (jax.devices()[0].device_kind or "").lower()
    return jax.default_backend() == "tpu" and (
        "v5e" in device_kind or "v5 lite" in device_kind
    )


def _transformer_model_and_loss():
    """The transformer row's model/loss: a ViT-S-class StreamFormer
    (patch 20 -> 24x32 = 768 tokens at 480x640, dim 512, depth 8, bf16
    activations on the MXU) regressing the same 8 corners, so it trains
    on the UNMODIFIED cube stream. Sized so the step is compute-bound —
    the headline CNN is memory-bound by design, and this row evidences
    the train layer can keep an MXU busy. Geometry
    choices are MXU/HBM-driven: 768 tokens (vs 1200 at patch 16) keeps
    the materialized f32 score tensor at 75 MB/layer — the measured
    per-layer softmax HBM cost at patch 16 (368 MB, ~2.2 ms/layer) held
    the step at 18% MFU — and 4 heads give head_dim 128, a full lane
    width."""
    from blendjax.models import StreamFormer
    from blendjax.train import corner_loss

    model = StreamFormer(
        patch=20, dim=512, depth=8, num_heads=4, num_outputs=16
    )

    def loss_fn(state, params, batch):
        pred = state.apply_fn({"params": params}, batch["image"])
        return corner_loss(
            pred.reshape(-1, 8, 2), batch["xy"],
            image_shape=batch["image"].shape[1:3],
        )

    return model, loss_fn


def measure_transformer_row(chunk: int) -> dict:
    """The train layer's non-toy performance row:
    StreamFormer training on the LIVE tile stream — the decoded frames
    feed its patch embedding through the identical pipeline the
    headline uses — plus the transfers-free step-alone rate and a
    ``cost_analysis()``-based MFU for both. CubeRegressor remains the
    headline for cross-round comparability."""
    import jax

    model, loss_fn = _transformer_model_and_loss()
    row: dict = {
        "model": "StreamFormer patch20 dim512 depth8 heads4 (bf16)",
    }
    alone = measure_step_alone(chunk, model=model, loss_fn=loss_fn)
    row["step_alone"] = alone
    live = measure(ENCODING, chunk, 256, 60.0, with_stages=False,
                   model=model, loss_fn=loss_fn)
    row["value"] = live["value"]
    row["live"] = {
        k: live[k]
        for k in ("seconds", "images", "final_loss", "instances", "chunk")
    }
    if _is_v5e():
        fl = measure_model_flops(
            model=model, loss_fn=loss_fn, label="StreamFormer fwd+bwd"
        )
        row["model_flops"] = fl
        row["mfu_live"] = round(
            live["value"] * fl["flops_per_image"] / V5E_PEAK_FLOPS, 4
        )
        row["mfu_step_alone"] = round(
            alone["img_s"] * fl["flops_per_image"] / V5E_PEAK_FLOPS, 4
        )
    # Long-sequence sub-row: the same model on 960x1280 frames -> 3072
    # patch tokens (4x the headline row), step-alone only (the live
    # stream is 480x640) — evidences the long-context train path on
    # real hardware in the driver record. attn_backend='auto' resolves
    # by blendjax.ops.attention's policy (the fused kernel from 24 MiB
    # of f32 scores a call up, measured on the chip; this shape has
    # 604 MB). remat off: activations fit at this size and remat
    # measured 31.3 -> 24.8 img/s.
    import jax.numpy as jnp

    from blendjax.models import StreamFormer
    from blendjax.ops.attention import auto_picks_flash

    long_model = StreamFormer(
        patch=20, dim=512, depth=8, num_heads=4, num_outputs=16,
        attn_backend="auto",
    )
    long_shape, long_batch = (960, 1280), 4
    tokens = (
        (long_shape[0] // long_model.patch)
        * (long_shape[1] // long_model.patch)
    )
    long_alone = measure_step_alone(
        chunk=4, calls=4, model=long_model, loss_fn=loss_fn,
        shape=long_shape, batch=long_batch,
    )
    # derived from the measured model's own geometry, so the
    # reported backend cannot diverge from what actually dispatched
    probe_q = jax.ShapeDtypeStruct(
        (long_batch, tokens, long_model.num_heads,
         long_model.dim // long_model.num_heads),
        jnp.bfloat16,
    )
    ls = {
        "tokens": tokens,
        "frame": list(long_shape),
        "attn_backend": (
            "flash(auto)" if auto_picks_flash(probe_q)
            else "xla(auto)"
        ),
        "step_alone": long_alone,
    }
    if _is_v5e():
        lfl = measure_model_flops(
            model=long_model, loss_fn=loss_fn,
            label="StreamFormer longseq fwd+bwd",
            shape=long_shape, batch=long_batch,
        )
        ls["flops_per_image"] = lfl["flops_per_image"]
        ls["mfu_step_alone"] = round(
            long_alone["img_s"] * lfl["flops_per_image"]
            / V5E_PEAK_FLOPS, 4
        )
    row["longseq"] = ls
    return row


def measure_precision_ab(chunk: int | None = None) -> dict:
    """Precision-policy A/B: ``bf16-grads`` vs ``bf16-compute``
    step-alone on the headline CNN AND the long-sequence transformer,
    with ``mfu_step_alone`` per leg (None off-v5e, where the v5e peak
    denominator would lie; the key is always present so CI can assert
    the row's shape on CPU).

    bf16-grads differentiates w.r.t. the bf16-cast params so the
    cross-chip gradient all-reduce carries half the bytes
    (:mod:`blendjax.train.precision`); step-alone on one chip it
    measures the cast overhead/benefit floor, and the same policy flag
    flows unchanged through the mesh builders where the all-reduce win
    is real. TPU runs the true bench geometries; other backends shrink
    both models so the row stays seconds-cheap in bench-smoke."""
    import jax

    from blendjax.models import CubeRegressor, StreamFormer
    from blendjax.train import corner_loss, resolve_policy

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cnn_kwargs: dict = {}
        cnn_shape, cnn_batch, cnn_chunk = SHAPE, BATCH, (chunk or CHUNK)
        tf_kwargs = dict(
            patch=20, dim=512, depth=8, num_heads=4, num_outputs=16
        )
        long_shape, long_batch, long_chunk, long_calls = (
            (960, 1280), 4, 4, 4
        )
    else:
        # shrunk geometry, batch = device count so the test/CI suite's
        # forced 8-device CPU mesh can shard the batch axis evenly;
        # sized for seconds, not fidelity — the structure is the
        # product, and the row costs 8 fresh jit compiles (2 models x
        # 2 policies x 2 step programs), so the models shrink too
        n_dev = max(1, len(jax.devices()))
        cnn_kwargs = {"features": (8, 16)}
        cnn_shape, cnn_batch, cnn_chunk = (32, 32), n_dev, 2
        tf_kwargs = dict(
            patch=8, dim=64, depth=1, num_heads=4, num_outputs=16
        )
        long_shape, long_batch, long_chunk, long_calls = (
            (64, 64), n_dev, 2, 2
        )

    def tf_loss(state, params, batch):
        pred = state.apply_fn({"params": params}, batch["image"])
        return corner_loss(
            pred.reshape(-1, 8, 2), batch["xy"],
            image_shape=batch["image"].shape[1:3],
        )

    def leg(policy_name: str) -> dict:
        policy = resolve_policy(policy_name)
        cnn = CubeRegressor(**cnn_kwargs, **policy.module_kwargs())
        cnn_alone = measure_step_alone(
            cnn_chunk, calls=2 if not on_tpu else 8, model=cnn,
            shape=cnn_shape, batch=cnn_batch, precision=policy,
        )
        tf = StreamFormer(**tf_kwargs, **policy.module_kwargs())
        long_alone = measure_step_alone(
            long_chunk, calls=long_calls, model=tf, loss_fn=tf_loss,
            shape=long_shape, batch=long_batch, precision=policy,
        )
        out = {
            "policy": policy.name,
            "cnn": {**cnn_alone, "mfu_step_alone": None},
            "longseq": {
                **long_alone,
                "tokens": (long_shape[0] // tf.patch)
                * (long_shape[1] // tf.patch),
                "mfu_step_alone": None,
            },
        }
        if _is_v5e():
            fl = measure_model_flops(
                model=cnn, label=f"CubeRegressor {policy.name}",
                shape=cnn_shape, batch=cnn_batch,
            )
            out["cnn"]["mfu_step_alone"] = round(
                cnn_alone["img_s"] * fl["flops_per_image"]
                / V5E_PEAK_FLOPS, 4
            )
            lfl = measure_model_flops(
                model=tf, loss_fn=tf_loss,
                label=f"StreamFormer longseq {policy.name}",
                shape=long_shape, batch=long_batch,
            )
            out["longseq"]["mfu_step_alone"] = round(
                long_alone["img_s"] * lfl["flops_per_image"]
                / V5E_PEAK_FLOPS, 4
            )
        return out

    row: dict = {"legs": {}}
    for name in ("bf16-compute", "bf16-grads"):
        row["legs"][name] = leg(name)
    base = row["legs"]["bf16-compute"]
    grads = row["legs"]["bf16-grads"]
    row["value"] = round(
        grads["cnn"]["img_s"] / max(base["cnn"]["img_s"], 1e-9), 3
    )
    row["longseq_ratio"] = round(
        grads["longseq"]["img_s"]
        / max(base["longseq"]["img_s"], 1e-9), 3
    )
    row["full_geometry"] = on_tpu
    return row


def measure_ingest_workers_ab(chunk: int, items: int | None = None,
                              time_cap: float = 30.0) -> dict:
    """Interleaved ingest_workers=1 vs 2 A/B on the live tile stream.

    Each leg keeps its stage breakdown's ingest slice: the per-shard
    ``ingest.recv.shard*`` spans evidence whether the second worker
    actually overlapped receive+decode (two busy shards) or just idled
    behind one hot producer, and the ``wire.*`` byte pair rides along
    for the compression accounting. ``value`` is the workers-2 /
    workers-1 throughput ratio (>1 means the pool wins on this host)."""
    items = min(192, MEASURE_ITEMS) if items is None else items
    row: dict = {}
    for workers in (1, 2):
        leg = measure(
            ENCODING, chunk, items, time_cap,
            with_stages=True, ingest_workers=workers,
        )
        stages = leg.get("stages", {})
        row[f"workers{workers}"] = {
            "img_s": leg["value"],
            "images": leg["images"],
            "seconds": leg["seconds"],
            "recv_spans": {
                k: v for k, v in stages.get("spans", {}).items()
                if k.startswith("ingest.recv")
            },
            "wire": {
                k: v for k, v in stages.get("counters", {}).items()
                if k.startswith("wire.")
            },
        }
    row["value"] = round(
        row["workers2"]["img_s"] / max(row["workers1"]["img_s"], 1e-9), 3
    )
    return row


def measure_live_overlap(chunk: int, items: int | None = None,
                         time_cap: float = 30.0,
                         inflight: int | None = None) -> dict:
    """Interleaved async-overlap A/B on the live tile stream: the SAME
    fused single-dispatch-per-step program driven by ``TrainDriver`` at
    ``inflight=1`` (the serialized dispatch-wait-dispatch baseline) vs
    ``inflight=N``.

    Each leg reports the driver's dispatch count (exactly one device
    call per step on the fused path — ``dispatch_per_step`` proves it),
    the ``decode.dispatch`` span count (0 = the standalone decode jit is
    eliminated), genuine ring-full ``host_blocks``, and the
    steps-in-flight high-water mark. ``value`` is the inflight-N /
    inflight-1 throughput ratio (>1 means keeping dispatches in flight
    pays)."""
    items = min(192, MEASURE_ITEMS) if items is None else items
    inflight = LIVE_OVERLAP_INFLIGHT if inflight is None else inflight
    # inflight<=1 would A/B a leg against itself (and burn the second
    # measurement for a meaningless ~1.0 ratio)
    inflight = max(2, int(inflight))
    row: dict = {}
    for n in (1, inflight):
        leg = measure(
            ENCODING, chunk, items, time_cap,
            with_stages=True, driver_inflight=n,
        )
        spans = leg.get("stages", {}).get("spans", {})
        drv = leg.get("driver", {})
        decode_calls = spans.get("decode.dispatch", {}).get("count", 0)
        train_calls = spans.get("train.dispatch", {}).get("count", 0)
        row[f"inflight{n}"] = {
            "img_s": leg["value"],
            "images": leg["images"],
            "seconds": leg["seconds"],
            "dispatches": drv.get("dispatches"),
            "steps_in_flight_hwm": drv.get("inflight_hwm"),
            "host_blocks": drv.get("host_blocks"),
            "decode_dispatch_count": decode_calls,
            "train_dispatch_count": train_calls,
        }
        if n != 1:
            # the inflight-N leg's completed frame traces (driver rows
            # retire every submitted batch, so a sampled frame that
            # reached the step is guaranteed to complete) — the
            # bench-smoke CI job asserts end-to-end completeness and
            # monotonic stage ordering on this report
            row["trace"] = leg.get("stages", {}).get("trace")
    one, many = row["inflight1"], row[f"inflight{inflight}"]
    row["decode_dispatch_eliminated"] = (
        one["decode_dispatch_count"] == 0
        and many["decode_dispatch_count"] == 0
    )
    # one jit call per driver step: the fused path's dispatch contract
    # (the bench-smoke CI job asserts this stays exactly 1.0)
    calls = many["train_dispatch_count"] + many["decode_dispatch_count"]
    row["dispatch_per_step"] = (
        round(calls / many["dispatches"], 3) if many["dispatches"] else None
    )
    row["value"] = round(many["img_s"] / max(one["img_s"], 1e-9), 3)
    return row


def measure_live_echo(items: int | None = None, time_cap: float = 25.0,
                      factors=None, capacity: int = 256,
                      inflight: int = 2) -> dict:
    """Interleaved data-echoing A/B on the live stream: the SAME
    decoded pipeline + ``TrainDriver``, echo off (supervised step) vs
    ``EchoingPipeline(max_echo_factor=f, emit_draws=True)`` driving
    the echo-FUSED step for each ``f`` in ``factors`` — gather +
    re-augmentation + loss + donated update in one jit
    (``make_echo_fused_step``).

    Each leg reports live img/s INTO the step (``steps * batch / s`` —
    the number echoing multiplies), the fresh frame rate, the unique
    fraction, final loss, and the contracts the bench-smoke CI job
    asserts: exact echo accounting (``echo.fresh + echo.echoed ==
    steps * batch``), exactly one DEVICE dispatch per driver step
    counting every step-cadence jit — the train call plus any
    standalone reservoir gather (``dispatch_per_step == 1.0``; the
    pre-fusion echo path cost 2.0 here and was only ever asserted
    train-dispatch-only), and the runtime donation audit
    (``donation_reuse`` / the ``train.donation_reuse`` gauge: ring and
    state buffer pointers stable across the window — updated in
    place, never copied; :mod:`blendjax.testing.donation`). ``value``
    is the largest echo leg's step-rate ratio over the echo-off
    leg."""
    import jax  # noqa: F401  (device backend must initialize first)

    from blendjax.data import EchoingPipeline, StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.models import CubeRegressor
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.testing.donation import DonationAudit
    from blendjax.train import (
        TrainDriver,
        make_echo_fused_step,
        make_supervised_step,
        make_train_state,
    )
    from blendjax.utils.metrics import metrics as reg

    items = min(128, MEASURE_ITEMS) if items is None else items
    factors = LIVE_ECHO_FACTORS if factors is None else tuple(factors)
    producer = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "datagen", "cube_producer.py",
    )
    mesh = create_mesh({"data": -1})
    sharding = batch_sharding(mesh)

    from blendjax.obs.trace import tracer

    def leg(factor: int | None) -> dict:
        reg.reset()
        tracer.reset()
        state = make_train_state(
            CubeRegressor(), np.zeros((BATCH, *SHAPE, 4), np.uint8),
            mesh=mesh,
        )
        fpi = _live_flops_per_image(CubeRegressor(), None)
        audit = DonationAudit()
        with PythonProducerLauncher(
            script=producer, num_instances=1, named_sockets=["DATA"],
            seed=0, proto="ipc",
            instance_args=[
                ["--shape", str(SHAPE[0]), str(SHAPE[1]),
                 "--batch", str(BATCH), "--encoding", ENCODING,
                 "--tile", *_TILE_ARGS, "--tile-rgba",
                 "--tile-capacity", TILE_CAPACITY,
                 "--trace-every", str(TRACE_EVERY)]
            ],
        ) as launcher:
            pipe = StreamDataPipeline(
                launcher.addresses["DATA"], batch_size=BATCH,
                sharding=sharding, timeoutms=60_000,
            )
            echo = None
            if factor is not None:
                # fused path: the pipeline emits draw TOKENS and the
                # reservoir gather+augment happens inside the train jit
                echo = EchoingPipeline(
                    pipe, capacity=capacity, max_echo_factor=factor,
                    emit_draws=True,
                )
                step = make_echo_fused_step(
                    reservoir_draw=echo.reservoir.draw
                )
            else:
                step = make_supervised_step(
                    mesh=mesh, batch_sharding=sharding
                )
            driver = TrainDriver(
                step, state, inflight=inflight, sync_every=16,
                flops_per_image=fpi,
                peak_flops=V5E_PEAK_FLOPS if fpi else None,
            )
            source = echo if echo is not None else pipe
            with source:
                it = iter(source)
                for _ in range(2):  # compile + fill queues
                    driver.submit(next(it))
                driver.drain()
                # donation audit marks: ring + state pointers at the
                # measured window's start (post-compile, so the donated
                # executables are the ones that run)
                audit.snapshot("state", driver.state.params)
                if echo is not None:
                    audit.snapshot("reservoir", echo.reservoir._buffers)
                reg.reset()
                drv0 = dict(driver.stats)
                e0 = dict(echo.stats) if echo is not None else None
                t0 = time.perf_counter()
                while True:
                    driver.submit(next(it))
                    dt = time.perf_counter() - t0
                    steps = driver.stats["steps"] - drv0["steps"]
                    if steps * BATCH >= items or dt > time_cap:
                        break
                final_loss = driver.drain()
                dt = time.perf_counter() - t0
                audit.snapshot("state", driver.state.params)
                if echo is not None:
                    audit.snapshot("reservoir", echo.reservoir._buffers)
                donation_ok = audit.stable("state") and (
                    echo is None or audit.stable("reservoir")
                )
                # surfaced in the run metrics too, so the record's
                # stage snapshot and the SLO watchdog can see a
                # donation regression without parsing this row
                reg.gauge("train.donation_reuse", float(donation_ok))
        report = reg.report()
        steps = driver.stats["steps"] - drv0["steps"]
        counters = report["counters"]
        train_calls = report["spans"].get(
            "train.dispatch", {}
        ).get("count", 0)
        decode_calls = report["spans"].get(
            "decode.dispatch", {}
        ).get("count", 0)
        # standalone reservoir gathers at the step cadence: ZERO on the
        # fused path (the draw rides inside the train jit); pre-fusion
        # this was one per step and dispatch_per_step read 2.0 when
        # honestly counted
        sample_calls = report["spans"].get(
            "echo.sample", {}
        ).get("count", 0)
        out = {
            "step_img_s": round(steps * BATCH / dt, 2),
            "steps": steps,
            "seconds": round(dt, 2),
            "final_loss": final_loss,
            # EVERY device call at the STEP cadence counts: the train
            # jit plus any standalone reservoir gather (pre-fusion the
            # gather was a second jit per step and this read 2.0; the
            # old row divided train calls alone and couldn't see it).
            # Reservoir inserts and the per-fresh-frame tile decode in
            # the drain thread stay data-layer dispatches at the FRAME
            # cadence — echoing exists to make that cadence lower —
            # and are reported beside, not divided in.
            "dispatch_per_step": round(
                (train_calls + sample_calls) / max(steps, 1), 3
            ),
            "echo_sample_dispatches": sample_calls,
            "decode_dispatch_count": decode_calls,
            "fused_draw": factor is not None,
            "donation_reuse": donation_ok,
            "donation_audit": audit.report(),
            "host_blocks": driver.stats["host_blocks"]
            - drv0["host_blocks"],
        }
        # Frame traces that completed in this leg (echo legs carry the
        # full recv -> decode -> reservoir -> step chain; sampled
        # frames that die unechoed in the reservoir simply don't
        # complete — expected for sampled tracing).
        out["trace"] = tracer.report()
        if echo is not None:
            st = echo.stats
            fresh = st["fresh"] - e0["fresh"]
            echoed = st["echoed"] - e0["echoed"]
            out.update({
                "max_echo_factor": factor,
                "fresh_img_s": round(
                    (st["inserted"] - e0["inserted"]) / dt, 2
                ),
                "unique_fraction": round(
                    fresh / max(fresh + echoed, 1), 4
                ),
                # measured-window accounting vs measured-window steps —
                # both deltas, so warmup can't skew the identity
                "accounting_exact": fresh + echoed == steps * BATCH,
                "saturated_waits": st["saturated_waits"]
                - e0["saturated_waits"],
                "echo_counters": {
                    k: int(v) for k, v in counters.items()
                    if k.startswith("echo.")
                },
            })
        else:
            out["unique_fraction"] = 1.0
        return out

    row: dict = {"off": leg(None)}
    for f in factors:
        row[f"echo{f}"] = leg(f)
    best = max(factors)
    row["value"] = round(
        row[f"echo{best}"]["step_img_s"]
        / max(row["off"]["step_img_s"], 1e-9), 3
    )
    row["accounting_exact"] = all(
        row[f"echo{f}"]["accounting_exact"] for f in factors
    )
    row["dispatch_per_step"] = max(
        row[k]["dispatch_per_step"] for k in row
        if isinstance(row[k], dict)
    )
    # the donation audit must hold on EVERY leg (CI-asserted): ring and
    # state buffers updated in place across the whole window
    row["donation_reuse"] = all(
        row[k]["donation_reuse"] for k in row if isinstance(row[k], dict)
    )
    return row


def measure_live_fleet(time_cap: float = 12.0, rate: float | None = None,
                       max_instances: int | None = None) -> dict:
    """Elastic producer-fleet A/B on the synthetic high-rate tier
    (docs/fleet.md): a FIXED fleet of 2 rate-capped producers vs an
    AUTOSCALED fleet that starts at 1 and lets the
    :class:`blendjax.fleet.FleetController` scale on live stall-doctor
    verdicts — the closed loop the observability stack was built for.
    Every producer is ``--rate``-capped, so each added instance buys a
    known supply increment and the producer-bound verdict is
    reproducible on any host (no Blender, no device step: the row runs
    identically on CPU CI).

    Each leg records img/s (whole window + the post-ramp second half),
    the instance-count trajectory at every controller tick, the
    scale-event log, and the run-length-compressed verdict sequence.
    ``value`` is the autoscaled leg's settled rate over the fixed
    leg's. A third UNTHROTTLED probe (one instance, no rate cap) shows
    the synthetic tier driving the same pipeline OUT of producer-bound
    — the scale-down regime Blender's ~5 img/s physically cannot
    reach. CI asserts ``scale_ups >= 1`` and ``seq_gaps == 0``."""
    from blendjax.data import StreamDataPipeline
    from blendjax.fleet import FleetController, FleetPolicy, synthetic_fleet
    from blendjax.obs.lineage import lineage
    from blendjax.utils.metrics import metrics as reg

    rate = FLEET_RATE if rate is None else rate
    max_instances = FLEET_MAX if max_instances is None else max_instances
    shape, batch = (32, 32), 4
    producer_args = ["--shape", str(shape[0]), str(shape[1]),
                     "--batch", str(batch), "--rate", str(rate)]

    def compress(seq):
        runs: list = []
        for kind in seq:
            if runs and runs[-1][0] == kind:
                runs[-1][1] += 1
            else:
                runs.append([kind, 1])
        return runs

    def leg(autoscale: bool) -> dict:
        reg.reset()
        lineage.reset()
        n_start = 1 if autoscale else 2
        trajectory: list = []
        verdicts: list = []
        with synthetic_fleet(
            n_start, shape=shape, batch=batch, rate=rate,
            bind_grace_s=0.5,
        ) as launcher:
            pipe = StreamDataPipeline(
                launcher.addresses["DATA"], batch_size=2 * batch,
                timeoutms=30_000,
            )
            ctrl = FleetController(
                launcher, connector=pipe,
                policy=FleetPolicy(
                    min_instances=n_start,
                    max_instances=max_instances if autoscale else n_start,
                    up_after=2, cooldown_s=2.0,
                ),
                diagnose=lambda: pipe.doctor(),
                instance_args=producer_args,
            )
            with pipe:
                it = iter(pipe)
                next(it)  # producers up, first batch through
                t0 = time.perf_counter()
                n = n_half = 0
                last_tick = 0.0
                while True:
                    n += int(next(it)["image"].shape[0])
                    now = time.perf_counter() - t0
                    if not n_half and now >= time_cap / 2:
                        n_half = n
                    if now - last_tick >= 0.5:
                        last_tick = now
                        # the controller tick runs HERE (main thread),
                        # not ctrl.start(): deterministic trajectories
                        # and no competing control thread in a bench
                        d = ctrl.tick()
                        verdicts.append(d["verdict"])
                        trajectory.append({
                            "t": round(now, 1),
                            "instances": d["instances"],
                            "verdict": d["verdict"],
                            "action": d["action"],
                        })
                    if now >= time_cap:
                        break
                dt = time.perf_counter() - t0
                instances_final = ctrl.state()["instances"]
        counters = reg.report()["counters"]
        settled = (
            (n - n_half) / (dt - time_cap / 2) if n_half else n / dt
        )
        return {
            "img_s": round(n / dt, 1),
            # ramp excluded: the rate the fleet settled at
            "settled_img_s": round(settled, 1),
            "frames": n,
            "seconds": round(dt, 2),
            "instances_final": instances_final,
            "trajectory": trajectory,
            "scale_events": list(ctrl.scale_events()),
            "verdicts": compress(verdicts),
            "seq_gaps": int(counters.get("wire.seq_gaps", 0)),
            "fleet_counters": {
                k: int(v) for k, v in counters.items()
                if k.startswith("fleet.")
            },
        }

    def unthrottled_probe(seconds: float = 6.0,
                          consumer_ms: float = 8.0) -> dict:
        """One UNTHROTTLED synthetic producer (~1,100 frames/s) against
        a consumer pinned at ``consumer_ms`` per batch (a stand-in
        train step): supply outruns consumption, the queue pins full,
        and the verdict must flip away from producer-bound — the
        scale-down regime the fleet controller needs CI evidence for."""
        reg.reset()
        lineage.reset()
        with synthetic_fleet(1, shape=shape, batch=batch) as launcher:
            pipe = StreamDataPipeline(
                launcher.addresses["DATA"], batch_size=2 * batch,
                timeoutms=30_000,
            )
            with pipe:
                it = iter(pipe)
                next(it)
                t0 = time.perf_counter()
                n = 0
                while time.perf_counter() - t0 < seconds:
                    n += int(next(it)["image"].shape[0])
                    time.sleep(consumer_ms / 1e3)
                dt = time.perf_counter() - t0
                verdict = pipe.doctor()
        return {
            "img_s": round(n / dt, 1),
            "consumer_ms": consumer_ms,
            "verdict": verdict.kind,
            # the tier's reason to exist in CI: supply outrunning the
            # consumer flips the verdict away from producer-bound
            "non_producer_bound": (
                not verdict.kind.startswith("producer-bound")
                and verdict.kind != "echo-saturated"
            ),
        }

    row: dict = {
        "fixed2": leg(False),
        "autoscaled": leg(True),
        "unthrottled": unthrottled_probe(),
        "rate_cap_per_instance": rate,
        "max_instances": max_instances,
    }
    row["value"] = round(
        row["autoscaled"]["settled_img_s"]
        / max(row["fixed2"]["settled_img_s"], 1e-9), 3
    )
    row["scale_ups"] = len([
        e for e in row["autoscaled"]["scale_events"]
        if e["action"] == "scale_up"
    ])
    row["seq_gaps"] = max(
        row["fixed2"]["seq_gaps"], row["autoscaled"]["seq_gaps"]
    )
    return row


def _wire_ab_messages(n: int, batch: int, h: int, w: int) -> list:
    """Deterministic in-memory recorded stream for the wire A/B: n
    prebatched cube-ish frames encoded with run-length "ndr" wire
    frames (pinned cap, so ONE packed spec / ONE jit compile). The
    equality leg decodes these SAME wire bytes two ways."""
    from blendjax.transport.wire import WireCompressState, encode_message

    state = WireCompressState()
    rng = np.random.default_rng(7)
    frames = []
    for i in range(n):
        img = np.zeros((batch, h, w, 4), np.uint8)
        x0 = 4 + (i % 5) * 9
        img[:, x0:x0 + 14, 8:40] = (i % 6) + 1
        xy = rng.integers(0, w, (batch, 8, 2)).astype(np.float32)
        frames.append(encode_message(
            {"btid": 0, "_prebatched": True, "image": img, "xy": xy},
            compress_rle=True, rle_cap=512, compress_min_bytes=1024,
            state=state,
        ))
    return frames


def measure_wire_equality(steps: int = 12, batch: int = 8,
                          shape=(64, 64)) -> dict:
    """The live_wire_ab equality contract, standalone: the SAME
    recorded wire bytes decoded two ways — "ndr" deferred to the fused
    train dispatch vs host-inflated "nd" fields — trained to the same
    step count from the same init. The deferred device expansion must
    train the SAME math (dev box: bit-identical; the CI bar allows f32
    reduction-reorder noise)."""
    import jax

    from blendjax.data import StreamDataPipeline
    from blendjax.models.cnn import CubeRegressor
    from blendjax.train.driver import TrainDriver
    from blendjax.train.steps import make_fused_tile_step, make_train_state
    from blendjax.transport.wire import decode_message

    h, w = shape
    model = CubeRegressor()
    frames = _wire_ab_messages(steps, batch, h, w)

    def run(deferred: bool) -> float:
        msgs = [decode_message(f, defer_rle=deferred) for f in frames]
        pipe = StreamDataPipeline(
            iter(msgs), batch_size=batch, emit_packed=True,
            place_in_driver=True,
        )
        drv = TrainDriver(
            make_fused_tile_step(),
            make_train_state(
                model, np.zeros((batch, h, w, 4), np.uint8),
                rng=jax.random.key(0),
            ),
            inflight=2, sync_every=0, place=pipe.feeder.place,
        )
        with pipe:
            for b in pipe:
                drv.submit(b)
        _, loss = drv.finish()
        return float(loss)

    ndr_loss = run(True)
    nd_loss = run(False)
    diff = abs(ndr_loss - nd_loss)
    return {
        "steps": steps,
        "ndr_loss": ndr_loss,
        "nd_loss": nd_loss,
        "max_abs_diff": diff,
        # the established f32 bar (reduction reorder only)
        "identical": diff <= 2e-6,
    }


def measure_live_wire_ab(time_cap: float | None = None,
                         rate: float | None = None) -> dict:
    """Wire-decode A/B (docs/performance.md "Closing the live-MFU
    gap"): the three levers of the live-vs-step-alone gap measured as
    one row on the synthetic tier.

    - ``step_alone``: the SAME fused one-dispatch step driven from a
      pre-placed packed batch — the consumer's ceiling with zero wire,
      zero host decode, zero placement (using the same step program on
      both sides isolates the input path instead of comparing two
      different XLA programs).
    - ``ndz`` leg: zlib wire, host inflate (through the sharded-pool
      decode-ahead path when engaged), feeder-free driver placement.
    - ``ndr`` leg: run-length wire; the expansion is DEFERRED into the
      fused train dispatch (``rle_groups`` decode plan), so the host
      inflate cost is structurally zero and ``dispatch_per_step`` stays
      exactly 1.0 with zero standalone decode dispatches — CI-asserted.
    - ``equality``: the SAME recorded wire bytes decoded both ways
      (deferred device expansion vs host inflate) trained to the same
      step count — f32 final losses must match (bit-identical on the
      dev box; the CI bar allows reduction-reorder noise).

    ``value`` / ``live_to_alone`` is the ndr leg's settled rate over
    the step-alone rate; CI asserts it against ``ratio_floor``
    (BLENDJAX_BENCH_WIRE_RATIO_FLOOR). Producers are rate-capped so
    the row measures the consumer's input path, not core contention
    with the renderer (dev box: 1 core, ratio ~1.0)."""
    import jax

    from blendjax.data import StreamDataPipeline
    from blendjax.fleet import synthetic_fleet
    from blendjax.models.cnn import CubeRegressor
    from blendjax.obs.lineage import lineage
    from blendjax.obs.trace import tracer
    from blendjax.train.driver import TrainDriver
    from blendjax.train.steps import make_fused_tile_step, make_train_state
    from blendjax.transport.wire import decode_message
    from blendjax.utils.metrics import metrics as reg

    time_cap = WIRE_TIME_CAP_S if time_cap is None else time_cap
    rate = WIRE_RATE if rate is None else rate
    (h, w), batch = (64, 64), 8
    model = CubeRegressor()

    def fresh_state():
        return make_train_state(
            model, np.zeros((batch, h, w, 4), np.uint8),
            rng=jax.random.key(0),
        )

    def step_alone_probe(calls: int = 24) -> dict:
        frames = _wire_ab_messages(2, batch, h, w)
        pipe = StreamDataPipeline(
            iter([decode_message(f, defer_rle=True) for f in frames]),
            batch_size=batch, emit_packed=True, place_in_driver=True,
        )
        it = iter(pipe)
        placed = pipe.feeder.place(next(it))
        drv = TrainDriver(
            make_fused_tile_step(), fresh_state(), inflight=4,
            sync_every=0,
        )
        drv.submit(dict(placed))
        drv.drain()  # compile outside the timed window
        t0 = time.perf_counter()
        for _ in range(calls):
            drv.submit(dict(placed))
        drv.drain()
        dt = time.perf_counter() - t0
        pipe.stop()
        return {
            "img_s": round(calls * batch / dt, 1),
            "ms_per_step": round(dt / calls * 1e3, 2),
        }

    def leg(wirekind: str) -> dict:
        reg.reset()
        lineage.reset()
        tracer.reset()
        extra = ["--wire", wirekind, "--trace-every", "4"]
        if wirekind == "ndr":
            extra += ["--rle-cap", "512"]
        with synthetic_fleet(
            1, shape=(h, w), batch=batch, rate=rate, extra_args=extra,
        ) as launcher:
            pipe = StreamDataPipeline(
                launcher.addresses["DATA"], batch_size=batch,
                emit_packed=True, place_in_driver=True,
                timeoutms=30_000,
            )
            drv = TrainDriver(
                make_fused_tile_step(), fresh_state(), inflight=4,
                sync_every=16, place=pipe.feeder.place,
            )
            with pipe:
                it = iter(pipe)
                drv.submit(next(it))  # producer up + jit compiled
                drv.drain()
                t0 = time.perf_counter()
                half = (drv.images_retired, 0.0)
                while True:
                    drv.submit(next(it))
                    el = time.perf_counter() - t0
                    if el <= time_cap / 2:
                        half = (drv.images_retired, el)
                    if el >= time_cap:
                        break
                drv.drain()
                dt = time.perf_counter() - t0
        r = reg.report()
        spans, counters = r["spans"], r["counters"]
        hists = r.get("histograms", {})
        settled = (
            (drv.images_retired - half[0]) / max(dt - half[1], 1e-9)
        )
        decode_calls = int(spans.get("decode.dispatch", {}).get("count", 0))
        train_calls = int(spans.get("train.dispatch", {}).get("count", 0))
        trace = tracer.report()
        wire_ms = trace.get("transitions", {}).get("trace.wire_ms", {})
        return {
            "img_s": round(drv.images_retired / max(dt, 1e-9), 1),
            "settled_img_s": round(settled, 1),
            "steps": int(drv.steps),
            "wire_bytes": int(counters.get("wire.compressed_bytes", 0)),
            "decoded_bytes": int(counters.get("wire.raw_bytes", 0)),
            "wire_compression": round(
                counters.get("wire.raw_bytes", 0)
                / max(counters.get("wire.compressed_bytes", 1), 1), 1,
            ),
            # host-side wire decode cost per message: the ndz leg's
            # zlib inflate histogram; structurally 0 on the ndr leg
            # (the expansion runs inside the train dispatch)
            "decode_ms_p95": round(
                float(hists.get("wire.inflate_ms", {}).get("p95", 0.0)),
                3,
            ),
            "wire_ms_p95": round(float(wire_ms.get("p95_ms", 0.0)), 3),
            "trace_completed": int(trace.get("completed", 0)),
            "decode_dispatch_count": decode_calls,
            "train_dispatch_count": train_calls,
            "dispatch_per_step": (
                round((train_calls + decode_calls) / drv.steps, 3)
                if drv.steps else None
            ),
            "host_blocks": int(drv.host_blocks),
            "seq_gaps": int(counters.get("wire.seq_gaps", 0)),
            "rle_counters": {
                k: int(v) for k, v in counters.items()
                if k.startswith("rle.")
            },
        }

    row: dict = {
        "step_alone": step_alone_probe(),
        "ndz": leg("ndz"),
        "ndr": leg("ndr"),
        "equality": measure_wire_equality(batch=batch, shape=(h, w)),
        "rate_cap": rate,
        "ratio_floor": WIRE_RATIO_FLOOR,
    }
    row["live_to_alone"] = round(
        row["ndr"]["settled_img_s"]
        / max(row["step_alone"]["img_s"], 1e-9), 3,
    )
    row["value"] = row["live_to_alone"]
    row["seq_gaps"] = max(row["ndz"]["seq_gaps"], row["ndr"]["seq_gaps"])
    return row


def measure_live_scenario(time_cap: float | None = None,
                          min_steps: int | None = None,
                          rate: float = 60.0) -> dict:
    """Closed-loop domain-randomization A/B (docs/scenarios.md): a
    2-producer synthetic fleet renders a 2-scenario space — ``easy``
    vs ``hard`` (irreducible label noise, the scenario a curriculum
    must find) — published over the duplex channel, streamed through
    the fused echo path, and trained with per-step loss attribution.

    Leg A (``fixed``) freezes the uniform mixture; leg B
    (``curriculum``) lets :class:`blendjax.scenario.ScenarioCurriculum`
    republish adapted mixture weights every few steps. Both legs hold
    the structural contracts CI asserts: EXACT per-scenario accounting
    (fresh + echoed sums to steps*batch across the declared scenarios),
    >= 2 distinct scenario ids observed, ``seq_gaps == 0``, and
    ``dispatch_per_step == 1.0`` (the echo draw rides inside the train
    jit; the only other per-step device interaction is the loss fetch
    the curriculum needs). The curriculum leg must additionally advance
    the space version >= 2 and shift mixture weight toward the
    high-loss scenario."""
    import jax  # noqa: F401  (device backend must initialize first)

    from blendjax.data import EchoingPipeline, StreamDataPipeline
    from blendjax.fleet import synthetic_fleet
    from blendjax.models import CubeRegressor
    from blendjax.obs.lineage import lineage
    from blendjax.scenario import (
        ScenarioCurriculum,
        ScenarioService,
        ScenarioSpace,
        accounting,
    )
    from blendjax.train import make_echo_fused_step, make_train_state
    from blendjax.utils.metrics import metrics as reg

    time_cap = SCENARIO_TIME_CAP_S if time_cap is None else time_cap
    min_steps = SCENARIO_MIN_STEPS if min_steps is None else min_steps
    shape, pbatch, tbatch = (32, 32), 4, 8
    # xy_jitter HALF the image side: the hard scenario's irreducible
    # label-noise loss dominates the early-training transient, so the
    # per-window loss ranking (the curriculum's signal) is stable run
    # to run — at 8px the transient could swamp the ~20% gap in an
    # unlucky window and flip an early update
    spec = (
        "easy:half_extent=u(0.8,1.2) / "
        "hard:half_extent=u(0.8,1.2),xy_jitter=16"
    )

    def leg(adaptive: bool) -> dict:
        reg.reset()
        lineage.reset()
        accounting.reset()
        space = ScenarioSpace.parse(spec)
        w0 = space.weights()
        svc = ScenarioService(space)
        try:
            with synthetic_fleet(
                2, shape=shape, batch=pbatch, rate=rate,
                scenario=True, bind_grace_s=0.5,
            ) as launcher:
                for i, addr in enumerate(launcher.addresses["CTRL"]):
                    svc.attach(i, addr)
                acked = svc.wait_acked(timeout=15)
                pipe = StreamDataPipeline(
                    launcher.addresses["DATA"], batch_size=tbatch,
                    timeoutms=30_000,
                )
                echo = EchoingPipeline(
                    pipe, capacity=64, max_echo_factor=4,
                    emit_draws=True,
                )
                step = make_echo_fused_step(
                    reservoir_draw=echo.reservoir.draw
                )
                state = make_train_state(
                    CubeRegressor(),
                    np.zeros((tbatch, *shape, 4), np.uint8),
                )
                curriculum = ScenarioCurriculum(
                    space, service=svc, every_steps=10, min_rows=4,
                    adapt_params=False, frozen=not adaptive,
                )
                steps = 0
                t0 = time.perf_counter()
                with echo:
                    it = iter(echo)
                    while True:
                        token = next(it)
                        # one fused jit per step — the span IS the
                        # dispatch-count evidence dispatch_per_step
                        # divides (same accounting as live_echo)
                        with reg.span("train.dispatch"):
                            state, m = step(state, token)
                        # per-step loss fetch: the curriculum's
                        # evidence (a sync, not an extra dispatch)
                        loss = float(m["loss"])
                        accounting.account_batch(token, loss=loss)
                        curriculum.step(1)
                        steps += 1
                        dt = time.perf_counter() - t0
                        if steps >= min_steps and (
                            adaptive is False or curriculum.updates >= 1
                        ):
                            break
                        if dt > time_cap:
                            break
                dt = time.perf_counter() - t0
        finally:
            svc.stop()
        report = reg.report()
        counters = report["counters"]
        ledger = accounting.report()
        totals = accounting.totals()
        declared_rows = sum(
            f + e for sid, (f, e) in totals.items()
            if sid in space.names
        )
        train_calls = report["spans"].get(
            "train.dispatch", {}
        ).get("count", 0)
        sample_calls = report["spans"].get(
            "echo.sample", {}
        ).get("count", 0)
        wf = space.weights()
        return {
            "steps": steps,
            "seconds": round(dt, 2),
            "step_img_s": round(steps * tbatch / max(dt, 1e-9), 1),
            "acked_before_start": acked,
            "space_version": space.version,
            "curriculum_updates": curriculum.updates,
            "weights_initial": {k: round(v, 4) for k, v in w0.items()},
            "weights_final": {k: round(v, 4) for k, v in wf.items()},
            "weight_shifted": wf["hard"] > w0["hard"] + 0.02,
            "distinct_ids": len(totals),
            "per_scenario": {
                sid: {
                    "fresh": f, "echoed": e,
                    "loss_p50": round(
                        ledger["scenarios"][sid]["loss"]["p50"], 5
                    ) if sid in ledger["scenarios"] else None,
                    "versions": ledger["scenarios"][sid]["versions"]
                    if sid in ledger["scenarios"] else {},
                }
                for sid, (f, e) in sorted(totals.items())
            },
            # EXACT: every drawn row attributed to a declared scenario,
            # fresh + echoed summing to steps * batch with zero slack
            "accounting_exact": declared_rows == steps * tbatch,
            "dispatch_per_step": round(
                (train_calls + sample_calls) / max(steps, 1), 3
            ),
            "seq_gaps": int(counters.get("wire.seq_gaps", 0)),
            "scenario_counters": {
                k: int(v) for k, v in counters.items()
                if k.startswith("scenario.")
            },
            "echo_saturated_waits": int(
                counters.get("echo.saturated_waits", 0)
            ),
        }

    row: dict = {
        "fixed": leg(False),
        "curriculum": leg(True),
        "high_loss": "hard",
        "space_spec": spec,
    }
    legs = (row["fixed"], row["curriculum"])
    row["accounting_exact"] = all(g["accounting_exact"] for g in legs)
    row["distinct_ids"] = min(g["distinct_ids"] for g in legs)
    row["dispatch_per_step"] = max(g["dispatch_per_step"] for g in legs)
    row["seq_gaps"] = max(g["seq_gaps"] for g in legs)
    # the headline: how much mixture weight the curriculum moved onto
    # the high-loss scenario (0.5 = it did nothing)
    row["value"] = row["curriculum"]["weights_final"]["hard"]
    return row


_RESUME_BATCH = 8
_RESUME_HW = 16
_RESUME_SEED = 11


def _resume_messages(n: int, skip: int = 0):
    """The deterministic message sequence both live_resume legs train
    on: resuming regenerates it and skips the consumed prefix, exactly
    like fast-forwarding a recorded stream."""
    rng = np.random.default_rng(_RESUME_SEED)
    for i in range(n):
        msg = {
            "_prebatched": True,
            "image": rng.integers(
                0, 255, (_RESUME_BATCH, _RESUME_HW, _RESUME_HW, 4),
                np.uint8,
            ),
            "xy": (
                rng.random((_RESUME_BATCH, 8, 2)) * _RESUME_HW
            ).astype(np.float32),
        }
        if i >= skip:
            yield msg


def _live_resume_child_main() -> int:
    """Child mode: train the deterministic stream over a REAL
    publisher socket with checkpointing on; write losses + structural
    evidence to --out. ``--resume`` restores train state + session
    (driver counters, lineage positions) from the snapshot dir first.
    The parent may SIGKILL this process at any time — everything a
    resume sees is what the async writer COMMITTED."""
    import argparse
    import threading

    ap = argparse.ArgumentParser()
    ap.add_argument("--live-resume-child", action="store_true")
    ap.add_argument("directory")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--ckpt-every", type=int, default=4)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--pace", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax  # noqa: F401  (backend init before any device work)

    from blendjax.checkpoint import (
        SnapshotManager,
        collect_session,
        restore_session,
    )
    from blendjax.data import StreamDataPipeline
    from blendjax.models import CubeRegressor
    from blendjax.obs.lineage import lineage
    from blendjax.train import (
        TrainDriver,
        make_supervised_step,
        make_train_state,
    )
    from blendjax.utils.metrics import metrics as reg

    t_build = time.monotonic()
    mgr = SnapshotManager(args.directory, keep=3)
    state = make_train_state(
        CubeRegressor(features=(8,)),
        np.zeros((_RESUME_BATCH, _RESUME_HW, _RESUME_HW, 4), np.uint8),
    )
    start = 0
    restored_driver = None
    if args.resume:
        restored = mgr.restore(state)
        assert restored is not None, "resume requested, no snapshot"
        state = restored.state
        restored_driver = restored.session["driver"]
        start = int(restored_driver["steps"])
        # restored lineage seq positions: the fresh publisher below
        # numbers from 0, which must read as a producer RESTART, not a
        # gap storm (wire.seq_gaps stays 0 across the restart)
        restore_session(restored.session, lineage=lineage)

    drv = TrainDriver(
        make_supervised_step(), state, inflight=2, sync_every=1,
        checkpoint=mgr, checkpoint_every=args.ckpt_every,
        session_state=lambda: collect_session(lineage=lineage),
    )
    if restored_driver is not None:
        drv.load_state_dict(restored_driver)
    # no build() here (the step set is plain jit, which this row wants:
    # it measures resume correctness, not compile) — stamp the clock
    # build() would have, so the row still reports cold-start wall time
    drv.startup_ms = (time.monotonic() - t_build) * 1e3

    addr_ready = threading.Event()
    addr_box: list = []

    def publish():
        # socket created ON this thread (BJX104); fresh numbering from
        # 0 every run — the restart the resumed lineage must absorb
        from blendjax.transport.channels import DataPublisherSocket

        # linger: the thread may finish publishing long before the
        # consumer drains — close() must not drop queued messages
        # (the default lingerms=0 would)
        ch = DataPublisherSocket(
            "tcp://127.0.0.1:*", btid=0, lingerms=30_000
        )
        addr_box.append(ch.addr)
        addr_ready.set()
        # a few margin messages past the step target: the pipeline's
        # prefetch ring pulls ahead of the train loop, and a PUSH
        # stream has no EOS — without margin the loop would block
        # prefetching past the final trained batch. The driver breaks
        # at --steps, so margin messages never train.
        for msg in _resume_messages(args.steps + 4, skip=start):
            ch.publish(**msg)
            if args.pace:
                time.sleep(args.pace)
        ch.close()

    pub = threading.Thread(target=publish, daemon=True)
    pub.start()
    assert addr_ready.wait(timeout=10), "publisher never bound"
    with StreamDataPipeline(
        [addr_box[0]], batch_size=_RESUME_BATCH, timeoutms=30_000,
    ) as pipe:
        for sb in pipe:
            drv.submit(sb)
            if drv.steps >= args.steps:
                break
    drv.finish()
    mgr.wait()
    mgr.close()
    pub.join(timeout=10)
    report = reg.report()
    counters = report["counters"]
    result = {
        "losses": [float(v) for v in drv.losses],
        "start": start,
        "steps": drv.steps,
        "checkpoints": drv.checkpoints,
        "ckpt_saves": int(counters.get("ckpt.saves", 0)),
        "ckpt_skipped": int(counters.get("ckpt.skipped", 0)),
        "ckpt_save_p95_ms": round(
            report["histograms"].get("ckpt.save_ms", {}).get("p95", 0.0),
            3,
        ),
        "seq_gaps": int(counters.get("wire.seq_gaps", 0)),
        "producer_restarts": int(
            counters.get("wire.producer_restarts", 0)
        ),
        "dispatch_per_step": round(
            report["spans"].get("train.dispatch", {}).get("count", 0)
            / max(drv.steps - start, 1), 3,
        ),
        "startup_ms": round(drv.startup_ms, 1),
        "time_to_first_step_ms": (
            round(drv.time_to_first_step_ms, 1)
            if drv.time_to_first_step_ms is not None else None
        ),
    }
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f)
    print("live-resume-child done", json.dumps(
        {k: result[k] for k in ("start", "steps", "seq_gaps")}
    ))
    return 0


def measure_live_resume(steps: int | None = None) -> dict:
    """Kill -9 / resume equality row (docs/checkpointing.md): an
    uninterrupted reference run, a paced run SIGKILLed after its first
    COMMITTED snapshot, and a resumed run continuing from that
    snapshot — all child processes over real loopback sockets. The
    headline is ``equality.identical``: the resumed f32 loss
    trajectory equals the uninterrupted one element for element."""
    import shutil
    import signal
    import subprocess
    import tempfile

    steps = RESUME_STEPS if steps is None else steps
    base = RESUME_DIR or tempfile.mkdtemp(prefix="bjx-live-resume-")
    os.makedirs(base, exist_ok=True)
    ref_dir = os.path.join(base, "ref")
    kill_dir = os.path.join(base, "kill")
    for d in (ref_dir, kill_dir):
        shutil.rmtree(d, ignore_errors=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # children of a process that holds the chip

    bench_path = os.path.abspath(__file__)

    def child(extra, timeout=240.0):
        proc = subprocess.run(
            [sys.executable, bench_path, "--live-resume-child", *extra],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=timeout,
        )
        assert proc.returncode == 0, proc.stdout[-2000:]
        return proc.stdout

    def load(path):
        with open(path) as f:
            return json.load(f)

    ref_out = os.path.join(base, "ref.json")
    child([ref_dir, "--steps", str(steps), "--ckpt-every", "4",
           "--out", ref_out])
    ref = load(ref_out)

    # kill leg: paced so >= 1 snapshot commits well before the run ends
    proc = subprocess.Popen(
        [sys.executable, bench_path, "--live-resume-child", kill_dir,
         "--steps", str(steps), "--ckpt-every", "4", "--pace", "0.4"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    from blendjax.checkpoint import committed_steps

    committed = False
    deadline = time.monotonic() + 180
    try:
        while time.monotonic() < deadline:
            if committed_steps(kill_dir):
                committed = True
                break
            if proc.poll() is not None:
                break  # child died pre-commit: don't burn the deadline
            time.sleep(0.05)
    finally:
        if proc.poll() is None:
            os.kill(proc.pid, signal.SIGKILL)
    kill_out, _ = proc.communicate(timeout=60)
    killed_mid_run = proc.returncode == -signal.SIGKILL

    res_out = os.path.join(base, "res.json")
    child([kill_dir, "--steps", str(steps), "--ckpt-every", "4",
           "--resume", "--out", res_out])
    res = load(res_out)

    diffs = [
        abs(a - b) for a, b in zip(ref["losses"], res["losses"])
    ]
    identical = (
        len(ref["losses"]) == len(res["losses"]) == steps
        and ref["losses"] == res["losses"]
    )
    row = {
        "platform": "cpu",
        "steps": steps,
        "killed_mid_run": killed_mid_run,
        "committed_before_kill": committed,
        "resumed_at": res["start"],
        "equality": {
            "identical": identical,
            "compared": len(diffs),
            "max_abs_diff": max(diffs, default=float("inf")),
        },
        # every leg ran with checkpointing enabled: the contract is
        # exactly one train dispatch per step anyway (ckpt.save_ms
        # lives on the writer thread)
        "dispatch_per_step": max(
            ref["dispatch_per_step"], res["dispatch_per_step"]
        ),
        "seq_gaps": ref["seq_gaps"] + res["seq_gaps"],
        "restart_detected": res["producer_restarts"] >= 1,
        "startup_ms": res["startup_ms"],
        "time_to_first_step_ms": res["time_to_first_step_ms"],
        "ckpt": {
            "saves": ref["ckpt_saves"] + res["ckpt_saves"],
            "skipped": ref["ckpt_skipped"] + res["ckpt_skipped"],
            "save_p95_ms": ref["ckpt_save_p95_ms"],
        },
        "value": 1.0 if identical else 0.0,
    }
    if identical:
        shutil.rmtree(base, ignore_errors=True)
    else:
        # keep the evidence: CI uploads the snapshot dir on failure
        # (BLENDJAX_BENCH_RESUME_DIR points it into the workspace)
        row["snapshot_dir"] = base
        row["kill_leg_tail"] = (kill_out or "")[-500:]
    return row


_START_BATCH = 32
_START_HW = 64
_START_SEED = 23


def _start_messages(n: int):
    """Deterministic prebatched stream for the live_start legs: smooth
    render-like frames (gradient shading + low-amplitude noise), 512 KB
    per message (32 frames of 64x64x4). Two properties matter: the
    payload is big enough that serialize+copy is a real per-message
    cost (the regime the shm ring exists for — toy frames leave both
    wires step-overhead-bound), and it is COMPRESSIBLE, so the ndz
    codec actually compresses every message instead of engaging its
    adaptive incompressible-noise skip and shipping raw."""
    rng = np.random.default_rng(_START_SEED)
    y, x = np.mgrid[0:_START_HW, 0:_START_HW]
    ramp = (2 * x + 3 * y).astype(np.int64)[None, :, :, None]
    for i in range(n):
        noise = rng.integers(0, 8, (_START_BATCH, _START_HW, _START_HW, 4))
        yield {
            "_prebatched": True,
            "image": ((ramp + noise + 5 * i) % 256).astype(np.uint8),
            "xy": (
                rng.random((_START_BATCH, 8, 2)) * _START_HW
            ).astype(np.float32),
        }


def _live_start_child_main() -> int:
    """Child mode for the instant-start row: build the driver through
    ``TrainDriver.build`` (AOT step set + persistent compilation cache
    at the shared ``cache_dir``), train a deterministic stream over a
    real loopback socket on the requested wire (``ndz`` or ``shm``),
    and write startup/compile/throughput/accounting evidence to
    ``--out``. Fresh process per leg — that IS the cold/warm
    experiment."""
    import argparse
    import threading

    ap = argparse.ArgumentParser()
    ap.add_argument("--live-start-child", action="store_true")
    ap.add_argument("cache_dir")
    ap.add_argument("--wire", choices=("ndz", "shm"), default="ndz")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax  # noqa: F401  (backend init before any device work)

    from blendjax.data import StreamDataPipeline
    from blendjax.models import CubeRegressor
    from blendjax.train import TrainDriver
    from blendjax.utils.metrics import metrics as reg

    example = {
        k: v for k, v in next(iter(_start_messages(1))).items()
        if not k.startswith("_")
    }
    drv = TrainDriver.build(
        CubeRegressor(features=(8,)), example,
        aot=True, aot_cache_dir=args.cache_dir,
        inflight=2, sync_every=1,
    )

    addr_ready = threading.Event()
    drain_go = threading.Event()
    drain_n = 32
    margin = 4
    addr_box: list = []

    def publish():
        # socket created ON this thread (BJX104); wire-specific kwargs:
        # shm ships descriptors through the ring, ndz pays zlib on the
        # same content (compress_min_bytes=1 so every field compresses)
        from blendjax.transport.channels import DataPublisherSocket

        # shm ring provisioned past the training burst (the zmq legs
        # get the same courtesy from the socket buffers); the drain
        # phase below reuses slots, exercising the generation protocol
        kw = (
            {"shm": args.steps + 6} if args.wire == "shm"
            else {"compress_level": 6, "compress_min_bytes": 1}
        )
        ch = DataPublisherSocket(
            "tcp://127.0.0.1:*", btid=0, lingerms=30_000, **kw,
        )
        addr_box.append(ch.addr)
        addr_ready.set()
        # margin past the step target: the pipeline prefetches ahead
        # and a PUSH stream has no EOS (same shape as live_resume)
        for msg in _start_messages(args.steps + margin):
            ch.publish(**msg)
        # drain batch gated on the event so its serialize cost lands
        # INSIDE the timed drain window, not overlapped with training;
        # its own margin on top — the pipeline prefetches one ahead, so
        # the last counted message must never be the last published
        if drain_go.wait(timeout=120):
            for msg in _start_messages(drain_n + margin):
                ch.publish(**msg)
        ch.close()

    pub = threading.Thread(target=publish, daemon=True)
    pub.start()
    assert addr_ready.wait(timeout=10), "publisher never bound"
    t_loop = time.monotonic()
    with StreamDataPipeline(
        [addr_box[0]], batch_size=_START_BATCH, timeoutms=30_000,
    ) as pipe:
        it = iter(pipe)
        for sb in it:
            drv.submit(sb)
            if drv.steps >= args.steps:
                break
        drv.finish()
        wall = time.monotonic() - t_loop
        # transport drain: consume the remaining stream with no train
        # step in the loop. The end-to-end legs above are step-bound on
        # both wires (serialize overlaps training), so THIS is where
        # the wire shows: ndz pays zlib-6 per 512 KB message, shm pays
        # a memcpy out of the ring.
        t_drain = time.monotonic()
        drain_go.set()
        drained = 0
        for _ in range(margin + drain_n):
            next(it)
            drained += 1
        drain_wall = time.monotonic() - t_drain
        # join while the PULL side is still open: the publisher may
        # still be sending its final margin messages, and a PUSH with
        # no peer blocks forever
        pub.join(timeout=30)

    report = reg.report()
    counters = report["counters"]
    stats = drv.stats
    result = {
        "wire": args.wire,
        "losses": [float(v) for v in drv.losses],
        "steps": drv.steps,
        "startup_ms": round(stats["startup_ms"], 1),
        "time_to_first_step_ms": round(stats["time_to_first_step_ms"], 1),
        "compile_ms": round(drv.step.compile_ms, 1),
        "aot_signatures": len(drv.step.signatures),
        "aot_cache_hits": int(counters.get("train.aot_cache_hits", 0)),
        "aot_cache_misses": int(counters.get("train.aot_cache_misses", 0)),
        "aot_fallbacks": int(counters.get("train.aot_fallbacks", 0)),
        "imgs_per_s": round(drv.steps * _START_BATCH / max(wall, 1e-9), 1),
        "wire_imgs_per_s": round(
            drained * _START_BATCH / max(drain_wall, 1e-9), 1,
        ),
        "drained": drained,
        "seq_gaps": int(counters.get("wire.seq_gaps", 0)),
        "shm_reads": int(counters.get("wire.shm_reads", 0)),
        "shm_torn": int(counters.get("wire.shm_torn", 0)),
        "shm_fallbacks": int(counters.get("wire.shm_fallbacks", 0)),
        "dispatch_per_step": round(
            report["spans"].get("train.dispatch", {}).get("count", 0)
            / max(drv.steps, 1), 3,
        ),
    }
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f)
    print("live-start-child done", json.dumps(
        {k: result[k] for k in (
            "wire", "compile_ms", "aot_cache_hits", "aot_cache_misses",
        )}
    ))
    return 0


def measure_live_start(steps: int | None = None) -> dict:
    """Instant-start + zero-copy transport row (docs/performance.md
    "Instant start"): cold and warm AOT legs sharing one persistent
    cache dir (fresh processes — the restart experiment), plus a
    shared-memory-wire leg on the same deterministic stream. The
    headlines: ``warm_vs_cold_compile_ratio`` (CI pins warm strictly
    below cold), ``equality.identical`` (shm f32 losses == ndz's), and
    ``shm_vs_ndz_throughput``."""
    import shutil
    import subprocess

    from blendjax.train.aot import DEFAULT_CACHE_DIR

    steps = START_STEPS if steps is None else steps
    # A FIXED directory, emptied for the cold leg (a cache entry is
    # looked up by a key its path is part of, so a directory that moves
    # never hits). The children are handed it the way any machine hands
    # a process its cache: through JAX_COMPILATION_CACHE_DIR.
    base = os.path.join(DEFAULT_CACHE_DIR, "live_start")
    shutil.rmtree(base, ignore_errors=True)
    cache = os.path.join(base, "xla-cache")
    os.makedirs(cache)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # children of a process that holds the chip
    env["JAX_COMPILATION_CACHE_DIR"] = cache
    bench_path = os.path.abspath(__file__)

    def leg(tag: str, wire: str) -> dict:
        out = os.path.join(base, f"{tag}.json")
        proc = subprocess.run(
            [sys.executable, bench_path, "--live-start-child", cache,
             "--wire", wire, "--steps", str(steps), "--out", out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stdout[-2000:]
        with open(out) as f:
            return json.load(f)

    cold = leg("cold", "ndz")
    warm = leg("warm", "ndz")
    shm = leg("shm", "shm")

    identical = (
        len(warm["losses"]) == len(shm["losses"]) == steps
        and warm["losses"] == shm["losses"]
    )
    ok = (
        identical
        and cold["aot_cache_misses"] > 0 and cold["aot_cache_hits"] == 0
        and warm["aot_cache_hits"] == warm["aot_signatures"]
        and warm["aot_cache_misses"] == 0
        and warm["compile_ms"] < cold["compile_ms"]
    )
    keys = ("startup_ms", "time_to_first_step_ms", "compile_ms",
            "aot_signatures", "aot_cache_hits", "aot_cache_misses",
            "aot_fallbacks", "imgs_per_s", "wire_imgs_per_s",
            "seq_gaps", "shm_torn", "dispatch_per_step")
    row = {
        "platform": "cpu",
        "steps": steps,
        "cold": {k: cold[k] for k in keys},
        "warm": {k: warm[k] for k in keys},
        "shm": {k: shm[k] for k in keys + ("shm_reads", "shm_fallbacks")},
        "warm_vs_cold_compile_ratio": round(
            warm["compile_ms"] / max(cold["compile_ms"], 1e-9), 3,
        ),
        # transport-drain rate ratio, not the end-to-end train rate
        # (both wires are step-bound end to end — serialize overlaps
        # training — so only the drain phase can show the wire)
        "shm_vs_ndz_throughput": round(
            shm["wire_imgs_per_s"] / max(warm["wire_imgs_per_s"], 1e-9), 3,
        ),
        "equality": {
            "identical": identical,
            "compared": min(len(warm["losses"]), len(shm["losses"])),
        },
        "seq_gaps": cold["seq_gaps"] + warm["seq_gaps"] + shm["seq_gaps"],
        "shm_torn": shm["shm_torn"],
        "dispatch_per_step": max(
            cold["dispatch_per_step"], warm["dispatch_per_step"],
            shm["dispatch_per_step"],
        ),
        "value": 1.0 if ok else 0.0,
    }
    shutil.rmtree(base, ignore_errors=True)
    return row


def _multichip_live_legs(mesh_sizes=None, time_cap: float | None = None,
                         b_dev: int = 2, shape=(16, 16)) -> dict:
    """The in-process body of the ``multichip_live`` row: the live
    pipeline on a named mesh at each requested size, fixed per-chip
    batch (weak scaling). Requires the process to already hold >=
    max(mesh_sizes) devices — the bench parent runs this in a
    subprocess via ``bench.py --multichip-live`` (see
    :func:`measure_multichip_live`); tests call it directly on their
    8-device CPU mesh.

    Each leg: 2 unthrottled synthetic producers (blendjax.fleet) ->
    ShardedHostIngest (2 workers) -> DeviceFeeder mesh placement ->
    MeshTrainDriver (pinned-sharding step, inflight=4). Per-chip batch
    stays fixed so the global batch grows with the mesh — the regime
    real data parallelism runs in, and the one that amortizes every
    per-batch host cost (ingest pop, placement call, dispatch) over N
    chips' worth of images."""
    import jax
    import jax.numpy as jnp

    from blendjax.data import StreamDataPipeline
    from blendjax.fleet import synthetic_fleet
    from blendjax.models import CubeRegressor
    from blendjax.obs.lineage import lineage
    from blendjax.parallel import create_mesh
    from blendjax.train import MeshTrainDriver
    from blendjax.utils.metrics import metrics as reg

    mesh_sizes = tuple(mesh_sizes or MULTICHIP_MESHES)
    time_cap = MULTICHIP_TIME_CAP_S if time_cap is None else time_cap
    avail = len(jax.devices())
    fit = tuple(n for n in mesh_sizes if n <= avail)
    if not fit:
        # name the misconfiguration instead of dying on fit[0] below
        # (the parent would only see an opaque subprocess rc=1)
        raise ValueError(
            f"no requested mesh size {mesh_sizes} fits the {avail} "
            "available devices — check BLENDJAX_BENCH_MULTICHIP_MESHES"
        )
    mesh_sizes = fit
    legs: dict = {}
    seq_gaps = 0

    def one_leg(n_dev: int) -> dict:
        nonlocal seq_gaps
        reg.reset()
        lineage.reset()
        gb = b_dev * n_dev
        mesh = create_mesh(
            {"data": n_dev}, devices=jax.devices()[:n_dev]
        )
        with synthetic_fleet(
            2, shape=shape, batch=gb, bind_grace_s=0.5
        ) as launcher:
            drv = MeshTrainDriver.build(
                CubeRegressor(features=(4,), dtype=jnp.float32), mesh,
                np.zeros((gb, *shape, 4), np.uint8),
                sync_every=0, inflight=4,
            )
            with StreamDataPipeline(
                launcher.addresses["DATA"], batch_size=gb, mesh=mesh,
                ingest_workers=2, timeoutms=30_000,
            ) as pipe:
                it = iter(pipe)
                for _ in range(4):  # compile (twice: donated layouts)
                    drv.submit(next(it))
                drv.drain()
                reg.reset()  # spans cover the measured window only
                steps0, blocks0 = drv.steps, drv.host_blocks
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < time_cap:
                    drv.submit(next(it))
                final_loss = drv.drain()
                dt = time.perf_counter() - t0
        steps = drv.steps - steps0
        spans = reg.report()["spans"]
        train_calls = spans.get("train.dispatch", {}).get("count", 0)
        decode_calls = spans.get("decode.dispatch", {}).get("count", 0)
        gaps = lineage.total_gaps()
        seq_gaps += gaps
        return {
            "img_s": round(steps * gb / dt, 1),
            "steps": steps,
            "global_batch": gb,
            "per_chip_batch": b_dev,
            "seconds": round(dt, 2),
            "host_blocks": drv.host_blocks - blocks0,
            "train_dispatch_count": train_calls,
            "decode_dispatch_count": decode_calls,
            "dispatch_per_step": (
                round((train_calls + decode_calls) / steps, 3)
                if steps else None
            ),
            "seq_gaps": gaps,
            "final_loss": final_loss,
        }

    # Interleaved passes, best-of per mesh size (the headline rows'
    # window-noise defense): the dispatch/gap contracts must hold on
    # EVERY pass — a kept best-throughput leg can't hide a contract
    # breach from a discarded one.
    contract_ok = True
    for _ in range(max(1, MULTICHIP_PASSES)):
        for n_dev in mesh_sizes:
            got = one_leg(n_dev)
            contract_ok = contract_ok and (
                got["dispatch_per_step"] == 1.0
                and got["decode_dispatch_count"] == 0
            )
            key = str(n_dev)
            if key not in legs or got["img_s"] > legs[key]["img_s"]:
                legs[key] = got
    row: dict = {
        "legs": legs,
        "seq_gaps": seq_gaps,
        "b_dev": b_dev,
        "passes": max(1, MULTICHIP_PASSES),
        "contracts_held_every_pass": contract_ok,
        # Scaling on a FORCED CPU mesh is bounded by real cores: the 8
        # virtual devices share this many, so read the efficiency
        # against min(cores, mesh) — on real multi-chip hardware each
        # mesh step runs on its own silicon and the same row reads
        # near-linear.
        "cpu_count": os.cpu_count(),
    }
    first, last = str(mesh_sizes[0]), str(mesh_sizes[-1])
    if first != last and legs[first]["img_s"]:
        speedup = legs[last]["img_s"] / legs[first]["img_s"]
        row["speedup"] = round(speedup, 3)
        row["scaling_efficiency"] = round(
            speedup * mesh_sizes[0] / mesh_sizes[-1], 3
        )
        row["value"] = row["speedup"]
    # the contracts CI asserts, lifted from the LARGEST mesh leg (the
    # one where a broken invariant would hide best)
    row["dispatch_per_step"] = legs[last]["dispatch_per_step"]
    row["decode_dispatch_eliminated"] = all(
        leg["decode_dispatch_count"] == 0 for leg in legs.values()
    )
    return row


def _cpu_mesh_child(flag: str, timeout_s: float) -> dict:
    """Run ``bench.py <flag>`` in a SUBPROCESS on a forced 8-device CPU
    mesh and return the JSON line it prints. This process's backend is
    already initialized with the real device topology (and holds the
    chip, when there is one), and
    ``xla_force_host_platform_device_count`` only takes effect before
    first use — so the mesh legs are CPU rows, and say so. A child that
    fails raises."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag],
        capture_output=True, text=True, timeout=timeout_s,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    lines = [
        ln for ln in (proc.stdout or "").strip().splitlines()
        if ln.startswith("{")
    ]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"bench.py {flag}: rc={proc.returncode} "
            f"stderr={(proc.stderr or '')[-2000:]}"
        )
    return {**json.loads(lines[-1]), "platform": "cpu"}


def _force_cpu_mesh(n_devices: int = 8) -> None:
    """Child-side half of :func:`_cpu_mesh_child`: select the CPU
    platform with ``n_devices`` virtual devices, before the first
    backend query."""
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")


def measure_multichip_live(timeout_s: float = 420.0) -> dict:
    """The multichip legs (``bench.py --multichip-live``): weak-scaling
    img/s at mesh 1/2/4/8 with the structural contracts."""
    return _cpu_mesh_child("--multichip-live", timeout_s)


def _multichip_live_main() -> None:
    """``bench.py --multichip-live`` entry."""
    _force_cpu_mesh()
    print(json.dumps(_multichip_live_legs()))


def measure_live_device_ledger() -> dict:
    """The device-ledger contracts (blendjax.obs.devledger) exercised
    live on synthetic in-memory batches — no producers, pure CPU.

    Single-chip leg (this process): ``TrainDriver.build`` registers the
    AOT step set with the ledger, so the driver's MFU numerator comes
    from XLA's own cost model; the row measures one settled dispatch
    rate and computes BOTH MFU figures from it — cost-model
    (ledger-derived ``flops_per_image``) and hand-fed
    (``measure_model_flops`` on the identical architecture/geometry) —
    asserting they agree within 10%. ``device.collective_bytes`` must
    read 0 (nothing to sync on one chip), ``device.retraces`` 0 across
    the bucketed dispatches and EXACTLY 1 (signature attributed) after
    a deliberately unbucketed shape is injected twice (the second
    dispatch is a jit cache hit — a second count would mean the audit
    miscounts).

    Mesh leg (subprocess, ``bench.py --devledger-mesh``): the 8-device
    CPU mesh's data-parallel grad sync, where the ledger's HLO parse
    must report a nonzero all-reduce byte count matching the analytic
    expectation — param bytes x policy dtype width (+ the f32 loss
    scalar's own all-reduce).
    """
    from blendjax.models import CubeRegressor
    from blendjax.obs.devledger import ledger, measure_model_flops
    from blendjax.train.driver import TrainDriver
    from blendjax.utils.metrics import metrics as reg

    reg.reset()
    ledger.reset()
    shape, batch = (32, 32), BATCH
    model = CubeRegressor(features=(4,))
    full = {
        "image": np.zeros((batch, *shape, 4), np.uint8),
        "xy": np.zeros((batch, 8, 2), np.float32),
    }
    # explicit peak: CPU has no known-chip default, and the MFU gauge
    # needs a denominator — its VALUE is meaningless off-accelerator,
    # but both MFU figures share it, so the agreement contract holds
    # on any host
    drv = TrainDriver.build(
        model, full, aot=True, buckets=(4,),
        inflight=2, sync_every=0, peak_flops=1e12,
    )
    fpi_cost = drv.flops_per_image
    hand = measure_model_flops(
        model=CubeRegressor(features=(4,)),
        label="CubeRegressor devledger", shape=shape, batch=batch,
        memo=False,
    )
    fpi_hand = float(hand["flops_per_image"])

    # settled dispatch rate over the bucketed (compiled) path: full
    # batches plus one padded partial tail, the shapes the ladder holds
    from blendjax.data.batcher import pad_to_bucket

    steps = 24
    t0 = time.perf_counter()
    for _ in range(steps):
        drv.submit(dict(full))
    tail = {
        "image": np.zeros((3, *shape, 4), np.uint8),
        "xy": np.zeros((3, 8, 2), np.float32),
        "_partial": True,
    }
    drv.submit(pad_to_bucket(tail, buckets=(4,)))
    drv.drain()
    dt = max(time.perf_counter() - t0, 1e-9)
    rate = drv.images_retired / dt
    snap = reg.report()
    retraces_bucketed = int(snap["counters"].get("device.retraces", 0))
    collective_single = int(
        snap["gauges"].get("device.collective_bytes", -1)
    )

    # the deliberate retrace: lead 6 is in no ladder and carries no
    # `_partial` flag, so it reaches the fallback jit and compiles
    bad = {
        "image": np.zeros((6, *shape, 4), np.uint8),
        "xy": np.zeros((6, 8, 2), np.float32),
    }
    drv.submit(dict(bad))
    drv.submit(dict(bad))  # cache hit: must NOT count again
    drv.drain()
    snap = reg.report()
    retraces_after = int(snap["counters"].get("device.retraces", 0))
    events = ledger.report()["retraces"]["events"]
    offending = events[-1]["signature"] if events else None

    mfu_cost = rate * fpi_cost / drv.peak_flops if fpi_cost else None
    mfu_hand = rate * fpi_hand / drv.peak_flops
    rel_err = (
        abs(mfu_cost - mfu_hand) / mfu_hand if mfu_cost else None
    )
    row = {
        "mfu_source": drv.mfu_source,
        "flops_per_image_cost_model": fpi_cost,
        "flops_per_image_hand_fed": fpi_hand,
        "mfu_cost_model": mfu_cost,
        "mfu_hand_fed": mfu_hand,
        "mfu_rel_err": round(rel_err, 4) if rel_err is not None else None,
        "mfu_within_tol": rel_err is not None and rel_err <= 0.10,
        "collective_bytes_single_chip": collective_single,
        "retraces_bucketed": retraces_bucketed,
        "retraces_after_inject": retraces_after,
        "retrace_contract": (
            retraces_bucketed == 0 and retraces_after == 1
        ),
        "offending_signature": offending,
        "signature_attributed": bool(
            offending and "(6," in offending
        ),
        "hbm_peak_bytes": snap["gauges"].get("device.hbm_peak_bytes"),
        "ledger_entries": len(ledger.report()["entries"]),
        "img_s": round(rate, 1),
    }
    row["value"] = row["mfu_rel_err"]
    row["mesh"] = _devledger_mesh_subprocess()
    mesh = row["mesh"]
    row["mesh_all_reduce_ok"] = bool(
        isinstance(mesh, dict) and mesh.get("within_tol")
    )
    if DEVLEDGER_EXPORT:
        try:
            with open(DEVLEDGER_EXPORT, "w", encoding="utf-8") as f:
                json.dump(
                    {
                        "single_chip": ledger.report(),
                        "mesh": mesh,
                        "contracts": {
                            k: row[k]
                            for k in (
                                "mfu_within_tol", "retrace_contract",
                                "collective_bytes_single_chip",
                                "mesh_all_reduce_ok",
                            )
                        },
                    },
                    f, default=str, indent=2,
                )
        except OSError as e:
            row["export_error"] = repr(e)[:200]
    return row


def _devledger_mesh_subprocess(timeout_s: float = 300.0) -> dict:
    """The mesh half of the ledger row (``bench.py --devledger-mesh``)."""
    return _cpu_mesh_child("--devledger-mesh", timeout_s)


def _devledger_mesh_main() -> None:
    """``bench.py --devledger-mesh`` entry: 8-device CPU data mesh,
    ``MeshTrainDriver.build`` with a SHARDED aot batch (the executable
    must see the live batch layout, or XLA compiles the replicated
    no-collectives program), then check the ledger's all-reduce byte
    count against the analytic DP grad-sync expectation."""
    _force_cpu_mesh()
    import jax

    from blendjax.models import CubeRegressor
    from blendjax.obs.devledger import ledger
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train.mesh_driver import MeshTrainDriver
    from blendjax.utils.metrics import metrics as reg

    n_dev = 8
    mesh = create_mesh({"data": n_dev}, devices=jax.devices()[:n_dev])
    bs = batch_sharding(mesh)
    shape, batch = (16, 16), 8
    img = np.zeros((batch, *shape, 4), np.uint8)
    aot_batch = {
        "image": jax.device_put(img, bs),
        "xy": jax.device_put(
            np.zeros((batch, 8, 2), np.float32), bs
        ),
    }
    drv = MeshTrainDriver.build(
        CubeRegressor(features=(4,), dtype=jax.numpy.float32), mesh,
        img, aot=True, aot_batch=aot_batch, buckets=(batch,),
        sync_every=0, inflight=2,
    )
    param_bytes = sum(
        int(np.prod(p.shape)) * p.dtype.itemsize
        for p in jax.tree_util.tree_leaves(drv.state.params)
    )
    # a couple of live dispatches through the compiled sharded path:
    # fallbacks/retraces here would mean the AOT layout didn't match
    drv.submit(dict(aot_batch))
    drv.submit(dict(aot_batch))
    drv.drain()
    snap = reg.report()
    ar = int(snap["gauges"].get("device.collective.all_reduce_bytes", 0))
    # analytic expectation: one all-reduce per grad leaf summing to the
    # param bytes (x f32 width, already in itemsize), plus slack for
    # the loss scalar's own sync and fusion rounding
    tol = 64 + 0.02 * param_bytes
    entries = ledger.report()["entries"]
    per_axis = {}
    for e in entries:
        c = e.get("collectives")
        if isinstance(c, dict) and c.get("per_axis"):
            per_axis = c["per_axis"]
    print(json.dumps({
        "chips": drv.chips,
        "all_reduce_bytes": ar,
        "expected_param_bytes": param_bytes,
        "tolerance_bytes": round(tol, 1),
        "within_tol": abs(ar - param_bytes) <= tol,
        "per_axis": per_axis,
        "collective_bytes": int(
            snap["gauges"].get("device.collective_bytes", 0)
        ),
        "mfu_source": drv.mfu_source,
        "flops_per_image": drv.flops_per_image,
        "aot_fallbacks": int(
            snap["counters"].get("train.aot_fallbacks", 0)
        ),
        "retraces": int(snap["counters"].get("device.retraces", 0)),
        "ledger": ledger.report(),
    }, default=str))


def _model_parallel_ab_legs(layouts=None, n_steps: int | None = None,
                            batch: int = 16, shape=(16, 16)) -> dict:
    """The in-process body of the ``model_parallel_ab`` row: one
    CubeRegressor, one deterministic f32 batch stream, trained
    end-to-end under each requested mesh layout; the legs diff
    throughput and the ledger's per-kind/per-axis collective bytes
    while the contracts pin that every layout computed the SAME
    program (final f32 loss equal to reduction rounding). Requires 8
    devices — the bench parent runs it in a subprocess via ``bench.py
    --model-parallel-ab``; tests call it directly on their 8-device
    CPU mesh.

    Per-axis attribution matches replica-group size to mesh axis size
    (``blendjax.obs.devledger.parse_collectives``), which is exact
    only when the layout's axis sizes are pairwise distinct — the
    2×2×2 leg is reported with ``attribution_ambiguous`` and skipped
    by the axis contracts."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import CubeRegressor
    from blendjax.obs.devledger import ledger
    from blendjax.parallel import (
        batch_sharding,
        resolve_layout,
        state_resident_bytes,
    )
    from blendjax.train.mesh_driver import MeshTrainDriver
    from blendjax.utils.metrics import metrics as reg

    layouts = tuple(layouts or MODEL_PARALLEL_LAYOUTS)
    n_steps = MODEL_PARALLEL_STEPS if n_steps is None else n_steps
    n_steps = max(3, n_steps)
    # one deterministic batch stream, shared by every leg: loss
    # equality is only meaningful if each layout consumes byte-equal
    # data in the same order
    rng = np.random.default_rng(20)
    host_batches = [
        {
            "image": rng.integers(
                0, 255, (batch, *shape, 4), dtype=np.uint8
            ),
            "xy": rng.normal(size=(batch, 8, 2)).astype(np.float32),
        }
        for _ in range(n_steps)
    ]

    def one_leg(name: str) -> dict:
        reg.reset()
        ledger.reset()
        layout = resolve_layout(name)
        mesh = layout.create_mesh()
        bs = batch_sharding(mesh)
        drv = MeshTrainDriver.build(
            CubeRegressor(features=(8, 16), dtype=jnp.float32), mesh,
            host_batches[0]["image"], layout=name, aot=True,
            aot_batch={
                k: jax.device_put(v, bs)
                for k, v in host_batches[0].items()
            },
            buckets=(batch,), sync_every=0, inflight=2,
        )
        # registration-time figures (memory_analysis of the compiled
        # sharded step) — read before the dispatch window resets reg
        snap0 = reg.report()["gauges"]
        resident = int(state_resident_bytes(drv.state))
        reg.reset()
        steps0 = drv.steps
        t0 = time.perf_counter()
        for b in host_batches:
            drv.submit({k: jax.device_put(v, bs) for k, v in b.items()})
        final_loss = drv.drain()
        dt = time.perf_counter() - t0
        steps = drv.steps - steps0
        spans = reg.report()["spans"]
        train_calls = spans.get("train.dispatch", {}).get("count", 0)
        # merge collectives over every registered executable of this
        # leg (the bucket ladder is one entry per shape here)
        per_kind: dict = {}
        per_axis: dict = {}
        total_bytes = 0
        for e in ledger.report()["entries"]:
            c = e.get("collectives")
            if not isinstance(c, dict):
                continue
            total_bytes += int(c.get("total_bytes", 0))
            for k, v in (c.get("per_kind") or {}).items():
                per_kind[k] = per_kind.get(k, 0) + int(v)
            for k, v in (c.get("per_axis") or {}).items():
                per_axis[k] = per_axis.get(k, 0) + int(v)
        sizes = [mesh.shape[a] for a in mesh.axis_names]
        return {
            "layout": layout.name,
            "mesh": dict(mesh.shape),
            "steps": steps,
            "final_loss": final_loss,
            "img_s": round(steps * batch / dt, 1) if dt else None,
            "seconds": round(dt, 3),
            "dispatch_per_step": (
                round(train_calls / steps, 3) if steps else None
            ),
            "flops_per_image": drv.flops_per_image,
            "state_resident_bytes_per_device": resident,
            "hbm_peak_bytes": snap0.get("device.hbm_peak_bytes"),
            "argument_bytes": snap0.get("device.argument_bytes"),
            "collective_total_bytes": total_bytes,
            "per_kind": per_kind,
            "per_axis": per_axis,
            # replica-group-size attribution is exact only when axis
            # sizes are pairwise distinct (devledger joins ties "|")
            "attribution_ambiguous": len(set(sizes)) != len(sizes),
        }

    legs = {name: one_leg(name) for name in layouts}

    def axis_bytes(leg: dict, axis: str) -> int:
        return sum(
            v for k, v in leg["per_axis"].items()
            if axis in k.split("|")
        )

    def fig(leg: dict) -> int:
        # the budget contract reads the ledger's hbm figure; resident
        # state is the fallback if a backend reports no memory stats
        return int(
            leg["hbm_peak_bytes"]
            or leg["state_resident_bytes_per_device"]
        )

    losses = [
        leg["final_loss"] for leg in legs.values()
        if leg["final_loss"] is not None
    ]
    loss_delta = (
        max(losses) - min(losses) if len(losses) == len(legs) else None
    )
    data_legs = [
        leg for leg in legs.values() if set(leg["mesh"]) == {"data"}
    ]
    fsdp_legs = [leg for leg in legs.values() if "fsdp" in leg["mesh"]]
    unambig = [
        leg for leg in legs.values() if not leg["attribution_ambiguous"]
    ]
    contracts = {
        "loss_equality_max_delta": loss_delta,
        "loss_equality": (
            loss_delta is not None
            and loss_delta <= MODEL_PARALLEL_LOSS_TOL
        ),
        "dispatch_per_step_one": all(
            leg["dispatch_per_step"] == 1.0 for leg in legs.values()
        ),
        # pure data parallelism needs exactly one collective: the grad
        # all-reduce — a gather/scatter there means a mis-sharded state
        "data_leg_all_reduce_only": all(
            leg["per_kind"].get("all-gather", 0) == 0
            and leg["per_kind"].get("reduce-scatter", 0) == 0
            and leg["per_kind"].get("all-reduce", 0) > 0
            for leg in data_legs
        ),
        # fsdp traffic (param all-gather-on-use + grad sync, attributed
        # to the fsdp axis) present exactly on fsdp layouts
        "fsdp_axis_bytes_iff_fsdp": all(
            (axis_bytes(leg, "fsdp") > 0) == ("fsdp" in leg["mesh"])
            for leg in unambig
        ),
        "fsdp_gather_traffic": all(
            leg["per_kind"].get("all-gather", 0)
            + leg["per_kind"].get("reduce-scatter", 0) > 0
            for leg in fsdp_legs if not leg["attribution_ambiguous"]
        ),
        "tp_axis_bytes_iff_tp": all(
            (axis_bytes(leg, "tp") > 0) == ("tp" in leg["mesh"])
            for leg in unambig
        ),
    }
    # the beyond-one-chip contract: under the forced per-device HBM
    # budget the replicated state does NOT fit, the fsdp-sharded one
    # does — and still trained end-to-end above
    rep = next(iter(data_legs), None)
    fsdp = next(
        (leg for leg in fsdp_legs if set(leg["mesh"]) <= {"data", "fsdp"}),
        None,
    ) or next(iter(fsdp_legs), None)
    if rep is not None and fsdp is not None:
        if MODEL_PARALLEL_HBM_BUDGET == "auto":
            budget = (fig(rep) + fig(fsdp)) // 2
        else:
            budget = int(MODEL_PARALLEL_HBM_BUDGET)
        contracts.update({
            "hbm_budget_bytes": budget,
            "hbm_exceeds_budget_replicated": fig(rep) > budget,
            "hbm_fits_budget_fsdp": fig(fsdp) <= budget,
            "fsdp_trains_end_to_end": bool(
                fsdp["steps"] == n_steps
                and fsdp["final_loss"] is not None
                and np.isfinite(fsdp["final_loss"])
            ),
            "fsdp_resident_ratio": (
                round(
                    rep["state_resident_bytes_per_device"]
                    / fsdp["state_resident_bytes_per_device"], 3
                )
                if fsdp["state_resident_bytes_per_device"] else None
            ),
        })
    contracts["all_ok"] = all(
        v for k, v in contracts.items()
        if isinstance(v, bool)
    )
    row = {
        "legs": legs,
        "global_batch": batch,
        "steps_per_leg": n_steps,
        "loss_tol": MODEL_PARALLEL_LOSS_TOL,
        "contracts": contracts,
        "cpu_count": os.cpu_count(),
    }
    if rep is not None and rep["img_s"]:
        for leg in legs.values():
            leg["throughput_vs_data"] = (
                round(leg["img_s"] / rep["img_s"], 3)
                if leg["img_s"] else None
            )
    row["value"] = contracts.get("loss_equality_max_delta")
    return row


def measure_model_parallel_ab(timeout_s: float = 420.0) -> dict:
    """The model-parallel A/B legs (``bench.py --model-parallel-ab``):
    the per-layout legs and the layout contracts."""
    return _cpu_mesh_child("--model-parallel-ab", timeout_s)


def _model_parallel_ab_main() -> None:
    """``bench.py --model-parallel-ab`` entry."""
    _force_cpu_mesh()
    print(json.dumps(_model_parallel_ab_legs(), default=str))


def measure_rl_hz(seconds: float = 3.0) -> dict:
    """Full REQ/REP rendezvous stepping rate, rendering off (the
    reference's '2000 Hz are easily achieved' row, ``Readme.md:95``).
    Pure CPU + IPC — no accelerator in the loop."""
    from blendjax.env.remote import RemoteEnv
    from blendjax.launcher import PythonProducerLauncher

    producer = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "control", "cartpole_producer.py",
    )
    with PythonProducerLauncher(
        script=producer, num_instances=1, named_sockets=["GYM"], seed=0,
        proto="ipc",
    ) as launcher:
        env = RemoteEnv(launcher.addresses["GYM"][0], timeoutms=30_000)
        try:
            env.reset()
            for _ in range(100):  # warm the rendezvous path
                _, _, done, _ = env.step(0.0)
                if done:
                    env.reset()
            steps = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                _, _, done, _ = env.step(0.0)
                steps += 1
                if done:
                    env.reset()
            dt = time.perf_counter() - t0
        finally:
            env.close()
    return {"value": round(steps / dt, 1), "unit": "steps/s",
            "steps": steps, "seconds": round(dt, 2), "platform": "cpu"}


def _live_rl_leg(prioritized: bool, steps: int | None = None,
                 envs: int | None = None, mesh=None,
                 checkpoint_dir: str | None = None,
                 ckpt_every: int = 0, resume: bool = False,
                 pace: float = 0.0, batch: int = 32,
                 capacity: int = 512, seed: int = 0) -> dict:
    """One end-to-end RL training leg: cartpole producer envs under an
    ActorPool -> TrajectoryReservoir -> one-dispatch DQN learner
    (:mod:`blendjax.rl`), with the contracts measured the way
    ``live_echo`` measures them — every device call at the STEP
    cadence counted (the fused learner jit plus any standalone
    reservoir gather, which the fused path makes zero), and the
    donation audit pinning ring + priority + param buffer pointers
    across the measured window.

    ``checkpoint_dir`` arms the session store (``ckpt_every`` learner
    steps); ``resume=True`` restores the latest snapshot and CONTINUES
    to the same total ``steps`` — the kill -9 leg's two halves.
    ``pace`` sleeps between learner steps so a parent can kill this
    leg mid-run deterministically."""
    import jax  # noqa: F401  (device backend must initialize first)

    from blendjax.env import BatchedRemoteEnv
    from blendjax.models import QNetwork
    from blendjax.rl import (
        ActorPool,
        HostQPolicy,
        RLTrainDriver,
        TrajectoryReservoir,
        make_dqn_step,
        make_rl_train_state,
        mesh_rl_step_kwargs,
    )
    from blendjax.testing.donation import DonationAudit
    from blendjax.utils.metrics import metrics as reg

    steps = RL_STEPS if steps is None else int(steps)
    envs = RL_ENVS if envs is None else int(envs)
    producer = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "control", "cartpole_producer.py",
    )
    reg.reset()
    reservoir = TrajectoryReservoir(
        capacity, rng=seed, prioritized=prioritized, mesh=mesh,
    )
    model = QNetwork(hidden=(32, 32), n_actions=3)
    state = make_rl_train_state(
        model, np.zeros((1, 4), np.float32), learning_rate=1e-3,
        mesh=mesh,
    )
    step_kwargs = (
        mesh_rl_step_kwargs(state, mesh) if mesh is not None else {}
    )
    step = make_dqn_step(reservoir, model.apply, gamma=0.98,
                         **step_kwargs)
    mgr = None
    if checkpoint_dir:
        from blendjax.checkpoint import SnapshotManager

        mgr = SnapshotManager(checkpoint_dir)
    audit = DonationAudit()
    with BatchedRemoteEnv(
        script=producer, num_envs=envs, seed=seed,
    ) as venv:
        pool = ActorPool(
            venv, reservoir,
            HostQPolicy(3, eps_steps=1500, seed=seed),
            # discrete index -> motor velocity (the cartpole action)
            action_map=np.array([-2.0, 0.0, 2.0], np.float32),
        )
        driver = RLTrainDriver(
            step, state, reservoir, actors=pool, mesh=mesh,
            batch_size=batch, min_fill=2 * batch, sync_every=8,
            inflight=2, checkpoint=mgr,
            checkpoint_every=ckpt_every,
        )
        start_step = 0
        restored_names: list = []
        if resume:
            restored = mgr.restore(state)
            if restored is None:
                raise RuntimeError(
                    f"--resume with no committed snapshot in "
                    f"{checkpoint_dir!r}"
                )
            driver.state = restored.state
            restored_names = driver.restore_session(restored.session)
            start_step = driver.steps
        fill_at_start = reservoir.size
        try:
            with pool:
                # warmup: reach min_fill + compile, and run the donated
                # executable a few times so its buffer assignment
                # settles before the audit marks (the multichip row's
                # "donated layouts" dance)
                for _ in range(min(3, max(steps - driver.steps - 1, 0))):
                    driver.train_step()
                driver.drain()
                audit.snapshot("params", driver.state.params)
                with reservoir.lock:
                    # under the lock: a concurrent actor insert donates
                    # these buffers, and a pointer read needs a live ref
                    audit.snapshot("ring", reservoir._buffers)
                    audit.snapshot("priorities", reservoir._priorities)
                reg.reset()
                drv0 = dict(driver.stats)
                res0 = (reservoir.fresh, reservoir.replayed)
                t0 = time.perf_counter()
                while driver.steps < steps:
                    driver.train_step()
                    if pace:
                        time.sleep(pace)
                final_loss = driver.drain()
                dt = time.perf_counter() - t0
                audit.snapshot("params", driver.state.params)
                with reservoir.lock:
                    audit.snapshot("ring", reservoir._buffers)
                    audit.snapshot("priorities", reservoir._priorities)
        finally:
            if mgr is not None:
                mgr.wait()
                mgr.close()
    donation_ok = all(
        audit.stable(k) for k in ("params", "ring", "priorities")
    )
    reg.gauge("train.donation_reuse", float(donation_ok))
    report = reg.report()
    spans = report["spans"]
    window_steps = driver.steps - drv0["steps"]
    train_calls = spans.get("train.dispatch", {}).get("count", 0)
    # standalone reservoir gathers at the step cadence: ZERO on the
    # fused path (the draw rides inside the learner jit) — the same
    # honest count live_echo keeps
    sample_calls = spans.get("rl.sample", {}).get("count", 0)
    drawn = (reservoir.fresh - res0[0]) + (reservoir.replayed - res0[1])
    returns = [r for _, r in pool.episode_returns]
    half = len(returns) // 2
    recent = returns[half:] if half else returns
    leg = {
        "prioritized": prioritized,
        "learner_steps": window_steps,
        "start_step": start_step,
        "total_steps": driver.steps,
        "seconds": round(dt, 2),
        "learner_steps_s": round(window_steps / max(dt, 1e-9), 1),
        "transitions_s": round(
            window_steps * batch / max(dt, 1e-9), 1
        ),
        "final_loss": final_loss,
        "dispatch_per_step": round(
            (train_calls + sample_calls) / max(window_steps, 1), 3
        ),
        "rl_sample_dispatches": sample_calls,
        "donation_reuse": donation_ok,
        "donation_audit": audit.report(),
        # the seq-style exact identities (CI-asserted): every drawn
        # row accounted exactly once, every env row inserted exactly
        # once
        "accounting_exact": drawn == window_steps * batch,
        "env_steps": pool.env_steps,
        "transitions_inserted": reservoir.inserts,
        "env_accounting_exact": pool.env_steps == reservoir.inserts,
        "episodes": pool.episodes,
        "mean_return": (
            round(float(np.mean(recent)), 2) if recent else None
        ),
        "mean_return_first_half": (
            round(float(np.mean(returns[:half])), 2) if half else None
        ),
        "replay_ratio": reservoir.stats["replay_ratio"],
        "policy_syncs": pool.policy_version,
        "sample_waits": driver.sample_waits,
        # the reward curve (bounded): (env_step, episode_return)
        "reward_curve": [
            [int(s), round(float(r), 1)]
            for s, r in pool.episode_returns[-100:]
        ],
    }
    if mesh is not None:
        leg["mesh_devices"] = int(
            np.prod([int(s) for s in mesh.shape.values()])
        )
    if mgr is not None:
        leg["ckpt_saves"] = driver.checkpoints
        leg["restored"] = restored_names
        leg["reservoir_fill_at_start"] = fill_at_start
    return leg


def measure_live_rl() -> dict:
    """The ``live_rl`` row: cartpole trained end to end by the
    actor-learner stack, four legs —

    - ``uniform`` / ``prioritized``: the sampling A/B on the local
      1-device path (same envs, same step budget);
    - ``mesh``: the prioritized leg on a forced 8-device CPU mesh in a
      subprocess (``bench.py --live-rl-mesh``), ring + priorities +
      state sharded over ``data``;
    - ``resume``: a paced child (``bench.py --live-rl-child``) is
      SIGKILLed after its first COMMITTED snapshot, then a second
      child restores the session and CONTINUES to the same total step
      count — the PR 11 survive-anything contract applied to RL.

    CI asserts (bench-smoke): ``dispatch_per_step == 1.0`` and
    ``donation_reuse`` on every local leg, exact transition
    accounting, ``mean_return >= RL_RETURN_FLOOR`` on the best leg,
    the mesh leg's single-dispatch contract, and the resume leg's
    continuation (killed mid-run after a commit; the resumed half
    starts where the snapshot ended and finishes the budget)."""
    import shutil
    import signal
    import subprocess
    import tempfile

    row: dict = {}
    contracts = []
    for name, prioritized in (("uniform", False), ("prioritized", True)):
        leg = _live_rl_leg(prioritized=prioritized)
        row[name] = leg
        contracts.append(
            leg["dispatch_per_step"] == 1.0 and leg["donation_reuse"]
            and leg["accounting_exact"]
        )
    row["dispatch_per_step"] = max(
        row[k]["dispatch_per_step"] for k in ("uniform", "prioritized")
    )
    row["donation_reuse"] = all(
        row[k]["donation_reuse"] for k in ("uniform", "prioritized")
    )
    row["accounting_exact"] = all(
        row[k]["accounting_exact"] and row[k]["env_accounting_exact"]
        for k in ("uniform", "prioritized")
    )
    best = max(
        (row[k]["mean_return"] or 0.0)
        for k in ("uniform", "prioritized")
    )
    row["mean_return"] = best
    row["return_floor"] = RL_RETURN_FLOOR
    row["reward_sane"] = best >= RL_RETURN_FLOOR
    row["value"] = best

    # -- mesh leg (a CPU child: see _cpu_mesh_child) -------------------
    row["mesh"] = _cpu_mesh_child("--live-rl-mesh", 300.0)

    # -- kill -9 -> resume leg ----------------------------------------
    base = RL_DIR or tempfile.mkdtemp(prefix="bjx-live-rl-")
    os.makedirs(base, exist_ok=True)
    kill_dir = os.path.join(base, "rl-kill")
    shutil.rmtree(kill_dir, ignore_errors=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    bench_path = os.path.abspath(__file__)
    resume_steps = max(24, min(RL_STEPS, 48))
    proc = subprocess.Popen(
        [sys.executable, bench_path, "--live-rl-child", kill_dir,
         "--steps", str(resume_steps), "--ckpt-every", "4",
         "--pace", "0.25"],
        env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    from blendjax.checkpoint import committed_steps

    committed = False
    deadline = time.monotonic() + 180
    try:
        while time.monotonic() < deadline:
            if committed_steps(kill_dir):
                committed = True
                break
            if proc.poll() is not None:
                break  # child died pre-commit
            time.sleep(0.05)
    finally:
        if proc.poll() is None:
            os.kill(proc.pid, signal.SIGKILL)
    kill_out, _ = proc.communicate(timeout=60)
    killed_mid_run = proc.returncode == -signal.SIGKILL

    res_out = os.path.join(base, "rl-res.json")
    proc2 = subprocess.run(
        [sys.executable, bench_path, "--live-rl-child", kill_dir,
         "--steps", str(resume_steps), "--ckpt-every", "4",
         "--resume", "--out", res_out],
        env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=240.0,
    )
    assert proc2.returncode == 0, proc2.stdout[-2000:]
    with open(res_out) as f:
        res = json.load(f)
    resumed = {
        "platform": "cpu",
        "steps": resume_steps,
        "killed_mid_run": killed_mid_run,
        "committed_before_kill": committed,
        "resumed_at": res["start_step"],
        "continued": bool(
            res["start_step"] > 0
            and res["total_steps"] == resume_steps
            and res["restored"]
        ),
        "restored_components": res["restored"],
        "dispatch_per_step": res["dispatch_per_step"],
        "reservoir_restored_fill": res["reservoir_fill_at_start"],
        "ckpt_saves": res.get("ckpt_saves", 0),
    }
    row["resume"] = resumed
    if resumed["continued"]:
        shutil.rmtree(base, ignore_errors=True)
    else:
        row["resume"]["snapshot_dir"] = base
        row["resume"]["kill_leg_tail"] = (kill_out or "")[-500:]

    row["contracts_held"] = all(contracts)
    return row


def _live_rl_mesh_main() -> None:
    """``bench.py --live-rl-mesh`` entry: one prioritized RL
    leg on the full mesh (ring + priorities + train state sharded over
    ``data``), print one JSON line."""
    _force_cpu_mesh()
    from blendjax.parallel import create_mesh

    mesh = create_mesh({"data": -1})
    print(json.dumps(
        _live_rl_leg(prioritized=True, steps=RL_MESH_STEPS, mesh=mesh)
    ))


def _live_rl_child_main() -> int:
    """``bench.py --live-rl-child`` entry: one checkpointed RL leg in a
    fresh process — the kill -9 / resume row's two halves share this
    body (``--resume`` restores the session store and continues to the
    same total step budget)."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--live-rl-child", action="store_true")
    ap.add_argument("directory")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--ckpt-every", type=int, default=4)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--pace", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # ONE leg body (``_live_rl_leg``) serves the A/B, mesh, AND resume
    # legs — the child only adds the no-commit guard the parent's
    # pre-kill race needs, and reports what the parent can't see:
    # where the resumed half started and which session components
    # actually restored
    from blendjax.checkpoint import committed_steps

    if args.resume and not committed_steps(args.directory):
        print("no committed snapshot to resume", file=sys.stderr)
        return 2
    leg = _live_rl_leg(
        prioritized=True, steps=args.steps, envs=2,
        checkpoint_dir=args.directory, ckpt_every=args.ckpt_every,
        resume=args.resume, pace=args.pace, batch=16, capacity=256,
    )
    keys = (
        "start_step", "total_steps", "restored",
        "reservoir_fill_at_start", "dispatch_per_step", "ckpt_saves",
        "mean_return",
    )
    blob = json.dumps({k: leg[k] for k in keys})
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob)
    print(blob)
    return 0


def _record(value: float, detail: dict) -> dict:
    """The one definition of the bench's JSON envelope."""
    return {
        "metric": "cube_640x480_stream+train images/sec/chip",
        "value": value,
        "unit": "images/s",
        "vs_baseline": round(value / BASELINE_IMG_PER_SEC, 3),
        "detail": detail,
    }


def _rows(primary: dict) -> list:
    """The add-on rows as ``(name, enabled, fn)``, in run order. A row
    whose children are forced onto the CPU backend says so itself
    (``"platform": "cpu"``); every other row runs on this process's
    backend and is stamped with it by :func:`_build_record`."""
    import jax

    chunk = primary["chunk"]
    tile = ENCODING == "tile"
    return [
        # Runtime ceiling: the same transfer -> decode -> step pipeline
        # with every wire message pre-staged on the host (ingest free).
        # Only meaningful when the headline ran the tile stream the
        # ceiling replays — comparing codecs would make the ratio lie.
        ("pipelined_ceiling", tile, lambda: measure_pipelined_ceiling(
            chunk, items=min(512, MEASURE_ITEMS)
        )),
        # Shorter full-frame row: tracks the non-sparse path (whole
        # frames, no temporal-delta assumption), stage breakdown
        # included so the row's bound is evidenced.
        ("raw_row", tile and RAW_ROW, _raw_row),
        ("live_overlap", tile and LIVE_OVERLAP,
         lambda: measure_live_overlap(chunk)),
        ("live_echo", tile and LIVE_ECHO, measure_live_echo),
        ("live_fleet", LIVE_FLEET, measure_live_fleet),
        ("live_wire_ab", LIVE_WIRE, measure_live_wire_ab),
        ("live_scenario", LIVE_SCENARIO, measure_live_scenario),
        ("live_resume", LIVE_RESUME, measure_live_resume),
        ("live_start", LIVE_START, measure_live_start),
        ("live_rl", LIVE_RL, measure_live_rl),
        ("multichip_live", MULTICHIP_LIVE, measure_multichip_live),
        ("live_device_ledger", LIVE_DEVLEDGER, measure_live_device_ledger),
        ("model_parallel_ab", MODEL_PARALLEL_AB, measure_model_parallel_ab),
        ("ingest_workers_ab", tile and INGEST_AB,
         lambda: measure_ingest_workers_ab(chunk)),
        # TPU-only: ~2,500 ViT-S fwd+bwd images would take an hour on a
        # CPU host, and the row's point is MXU evidence.
        ("transformer_row",
         tile and TRANSFORMER_ROW and jax.default_backend() == "tpu",
         lambda: measure_transformer_row(chunk)),
        ("precision_ab", PRECISION_AB, lambda: measure_precision_ab(chunk)),
        # Step-alone ceiling at the chunk configuration the passes
        # ACTUALLY ran (recorded in the pass result, not re-derived).
        ("step_alone", True, lambda: measure_step_alone(chunk)),
        # RL stepping rate (REQ/REP rendezvous, rendering off): host/IPC.
        ("rl_hz", True, measure_rl_hz),
    ]


def _raw_row() -> dict:
    """The full-frame row: lossless full-frame palette by default
    (producer ``--encoding pal``: 640x480x4 frames decode bit-exact from
    4-8x fewer bytes), or uncompressed frames."""
    pal = RAW_ENCODING == "pal"
    raw = measure(
        RAW_ENCODING, RAW_CHUNK if pal else 1,
        min(256 if pal else 128, MEASURE_ITEMS), 45.0, with_stages=True,
    )
    raw["MB_per_image"] = round(SHAPE[0] * SHAPE[1] * 4 / 1e6, 3)
    raw["MB_s"] = round(raw["value"] * raw["MB_per_image"], 1)
    if pal:
        counters = raw.get("stages", {}).get("counters", {})
        wire = counters.get("pal.wire_bytes", 0)
        decoded = counters.get("pal.decoded_bytes", 0)
        raw["codec"] = "full-frame palette (lossless, device gather)"
        if wire and decoded:
            raw["wire_MB_per_image"] = round(
                raw["MB_per_image"] * wire / decoded, 4
            )
            raw["compression"] = round(decoded / wire, 2)
    return raw


def _build_record() -> dict:
    """The whole measurement workload: ``BLENDJAX_BENCH_PASSES`` plain
    passes of the headline (best reported), then every enabled add-on
    row. A row that raises ends the run — nothing is turned into an
    error field of a record that still looks whole."""
    import jax

    from blendjax.train import configure_compilation_cache

    configure_compilation_cache()
    dev = jax.devices()[0]
    n_passes = max(1, int(os.environ.get("BLENDJAX_BENCH_PASSES", "4")))
    passes = [
        measure(ENCODING, CHUNK, MEASURE_ITEMS, TIME_CAP_S)
        for _ in range(n_passes)
    ]
    primary = max(passes, key=lambda r: r["value"])
    detail = dict(primary)
    ips = detail.pop("value")
    detail["platform"] = dev.platform
    detail["device_kind"] = dev.device_kind
    detail["device_count"] = jax.device_count()
    detail["passes"] = [
        {"value": p["value"], "seconds": p["seconds"]} for p in passes
    ]
    for name, enabled, fn in _rows(primary):
        if enabled:
            row = fn()
            row.setdefault("platform", dev.platform)
            detail[name] = row
    if "pipelined_ceiling" in detail:
        detail["utilization_vs_ceiling"] = round(
            ips / detail["pipelined_ceiling"]["img_s"], 3
        )
    detail["utilization"] = round(ips / detail["step_alone"]["img_s"], 3)
    if _is_v5e():
        # FLOPs-based MFU: achieved model FLOPs over the chip's peak,
        # for the live headline AND the transfers-free step-alone run —
        # the gap between the two is the pipeline; the gap from 1.0 is
        # the model's arithmetic intensity (a small CNN on uint8 frames
        # is memory-bound by design).
        fl = measure_model_flops()
        detail["model_flops"] = fl
        detail["mfu"] = round(
            ips * fl["flops_per_image"] / V5E_PEAK_FLOPS, 6
        )
        detail["mfu_step_alone"] = round(
            detail["step_alone"]["img_s"] * fl["flops_per_image"]
            / V5E_PEAK_FLOPS, 6
        )
    return _record(ips, detail)


def main() -> None:
    """Run the workload and print its one JSON line. Any failure
    propagates (non-zero exit) after the spawned producers are reaped."""
    from blendjax.launcher.launcher import kill_all_spawned

    try:
        print(json.dumps(_build_record()))
    finally:
        kill_all_spawned()


if __name__ == "__main__":
    if "--multichip-live" in sys.argv:
        sys.exit(_multichip_live_main())
    if "--devledger-mesh" in sys.argv:
        sys.exit(_devledger_mesh_main())
    if "--model-parallel-ab" in sys.argv:
        sys.exit(_model_parallel_ab_main())
    if "--live-resume-child" in sys.argv:
        sys.exit(_live_resume_child_main())
    if "--live-start-child" in sys.argv:
        sys.exit(_live_start_child_main())
    if "--live-rl-mesh" in sys.argv:
        sys.exit(_live_rl_mesh_main())
    if "--live-rl-child" in sys.argv:
        sys.exit(_live_rl_child_main())
    sys.exit(main())
