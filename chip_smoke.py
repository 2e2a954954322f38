"""The quickest proof that blendjax still starts on the chip.

``python3 chip_smoke.py`` drives the stream -> train path once, through
the entry points a user calls, on ONE TPU chip, at the stream's reference
sizes (``REAL``: batch 8, 480x640 RGBA, tile stream, chunk 16) with random
weights from ``--seed``, and checks what comes out:

1. *kernels*: the two Pallas tile decodes bit-exact against the numpy
   reference, the flash-attention kernel against ``reference_attention``;
2. *headline*: real ``cube_producer.py`` processes over ipc ->
   ``StreamDataPipeline(emit_packed=True)`` -> ``make_fused_tile_step`` ->
   ``TrainDriver``, for the cube CNN and then the ViT-S-class
   ``StreamFormer`` (the widest model the repo supports), with the
   invariants of that path asserted — one dispatch per step, no
   standalone decode, the decode kernel in what was lowered, native
   producers, no sequence gap, no compile after warm-up, a loss that falls;
3. *rl*: a few learner steps of the DQN actor-learner stack.

``--four-chips`` runs instead — and only — the mesh path and what it is
compared with: one recorded tile stream trained on one device and under
``MeshTrainDriver.build(layout=...)`` on a 2x2 mesh.

It is ONE process: it holds the chip, and the only children it starts
(producers, envs) never import JAX. Every phase prints one short JSON
line; the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
What the phase lines carry besides pass/fail (seconds, bytes, the
doctor's verdict) are observations of one run, not metrics. Any failed
phase makes ``ok`` false and the exit code 1. Without an accelerator it
runs nothing, prints its reason on stderr and no result on stdout, and
exits 2: it never trains on the CPU and reports success.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:  # the package is not installed; children get
    sys.path.insert(0, ROOT)  # the root from the launcher's PYTHONPATH

import numpy as np  # noqa: E402

# The cube scene's most changed tiles in one 480x640 frame (285 at 16x16
# tiles, 156 at 16x32, over 4,000 frames of four seeds) rounded up to 32.
# A smaller capacity grows mid-run and compiles the decode again.
TILE_CAPACITY = {(16, 16): "288", (16, 32): "160"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run streams and trains. ``REAL`` is what the chip gets;
    the CPU rehearsal in tests/ passes a tiny one."""

    shape: tuple = (480, 640)
    batch: int = 8
    chunk: int = 16
    tile_capacity: str = TILE_CAPACITY[16, 32]
    # Three faces and the background are 4 colours (2 bits), but about one
    # frame in 200 holds a fifth, and a batch with a wider index is another
    # wire shape: it breaks the chunk group and compiles a second step.
    tile_pal_bits: str = "4"
    producers: int = 2
    cnn_steps: int = 8        # driver steps after warm-up (x chunk updates)
    former_steps: int = 4
    # ViT-S-class: patch 20 is 768 tokens at 480x640, 4 heads of 128 lanes
    former: dict = dataclasses.field(default_factory=lambda: dict(
        patch=20, dim=512, depth=8, num_heads=4, num_outputs=16
    ))
    flash_shape: tuple = (4, 3072, 4, 128)
    attn_shape: tuple = (8, 1200, 12, 64)  # the benchmark's, through auto
    rl_steps: int = 12
    mesh_batches: int = 12    # --four-chips: recorded batches
    mesh_chunk: int = 2


REAL = Sizes()
WARMUP_STEPS = 2  # the donated step compiles twice (output layouts)
FLASH_ATOL = 2e-2  # tests/test_attention.py: a few bf16 ulps at |out|~2-4
F32_EXACT_ATOL = 5e-6  # blendjax.testing.equivalence / tests/test_mesh_driver.py
CUBE_PRODUCER = os.path.join(ROOT, "examples", "datagen", "cube_producer.py")
CARTPOLE_PRODUCER = os.path.join(
    ROOT, "examples", "control", "cartpole_producer.py"
)


class SmokeFailure(AssertionError):
    """A check of this script did not hold (``assert`` would vanish
    under ``python -O``)."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def device_record() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def peak_bytes_in_use():
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def counters() -> dict:
    from blendjax.utils.metrics import metrics

    return metrics.report()["counters"]


def decode_paths() -> dict:
    """``{path: traces}`` from the ``tiles.decode_path.*`` counters."""
    return {
        k.rsplit(".", 1)[1]: v for k, v in counters().items()
        if k.startswith("tiles.decode_path.")
    }


def lower_fused(step, state, batch):
    """The fused tile step lowered for ``batch`` (a packed tile group)."""
    return step.jits["tile"].lower(
        state, batch["_packed"], batch["_refs"], batch["_spec"],
        batch["_names"], batch["_geoms"], batch.get("_rle", ()),
    )


# -- phase 1: the kernels give the right answer ------------------------------


def _tile_batch(rng, shape, tile, batch, capacity):
    """A reference frame and ``batch`` frames differing from it in one
    random rectangle each, tile-delta encoded at ``capacity`` slots."""
    from blendjax.ops.tiles import TileDeltaEncoder, pack_batch

    h, w = shape
    ref = rng.integers(0, 255, (h, w, 4), np.uint8)
    frames = []
    for _ in range(batch):
        f = ref.copy()
        y, x = rng.integers(0, h // 2), rng.integers(0, w // 2)
        f[y:y + h // 3, x:x + w // 3] = rng.integers(
            0, 255, (h // 3, w // 3, 4), np.uint8
        )
        frames.append(f)
    enc = TileDeltaEncoder(ref, tile=tile)
    deltas = [tuple(a.copy() for a in enc.encode(f)) for f in frames]
    idx, tiles = pack_batch(deltas, enc.num_tiles, capacity=capacity)
    return ref, np.stack(frames), idx, tiles


def phase_kernels(sizes: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from blendjax.ops.tiles import (
        decode_tile_delta,
        decode_tile_delta_np,
        tile_hw,
        tile_ref,
    )

    rng = np.random.default_rng(seed)
    out: dict = {"phase": "kernels"}
    tpu = on_tpu()
    for tile, path in (((16, 32), "pallas_spatial"), (16, "pallas_scatter")):
        th, tw = tile_hw(tile)
        grid = (sizes.shape[0] // th) * (sizes.shape[1] // tw)
        ref, frames, idx, tiles = _tile_batch(
            rng, sizes.shape, tile, sizes.batch, min(288, grid)
        )
        before = counters().get(f"tiles.decode_path.{path}", 0)
        got = np.asarray(jax.jit(
            lambda r, i, tl: decode_tile_delta(r, i, tl, ref.shape)
        )(tile_ref(ref, tile), idx, tiles))
        np.testing.assert_array_equal(got, decode_tile_delta_np(ref, idx, tiles))
        np.testing.assert_array_equal(got, frames)
        took = counters().get(f"tiles.decode_path.{path}", 0) - before
        check(took == 1 or not tpu, f"decode at tile {tile} did not take {path}")
        out[f"decode_{th}x{tw}"] = path if took else "xla_scatter"
    out["flash_max_abs_diff"] = _attention_diffs(
        rng, sizes.flash_shape, "flash"
    )["out"]
    before = counters().get("attn.path.flash", 0)
    out["attn_auto_max_abs_diff"] = _attention_diffs(
        rng, sizes.attn_shape, "auto"
    )
    took = counters().get("attn.path.flash", 0) - before
    check(took == 1 or not tpu, f"auto at {sizes.attn_shape} stayed on xla")
    return out


def _attention_diffs(rng, shape, backend: str, mesh=None) -> dict:
    """``local_attention`` against ``reference_attention`` on bf16
    inputs: max abs diff of the output and of the three gradients, each
    under FLASH_ATOL. Off the TPU ``flash`` is the kernel interpreted
    and ``auto`` the XLA path. ``mesh``: batch-sharded over ``data``,
    declared as the mesh step builders declare it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from blendjax.ops.attention import batch_sharded_over, local_attention
    from blendjax.parallel.ring import reference_attention

    q, k, v, w = (
        jnp.asarray(rng.normal(size=shape), dt)
        for dt in (jnp.bfloat16,) * 3 + (jnp.float32,)
    )
    if mesh is not None:
        q, k, v, w = jax.device_put(
            (q, k, v, w), NamedSharding(mesh, PartitionSpec("data"))
        )

    def run(attend):
        def loss(q, k, v):
            o = attend(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w), o

        (_, o), grads = jax.jit(
            jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
        )(q, k, v)
        return o, *grads

    def attend(q, k, v):
        with batch_sharded_over(mesh):
            return local_attention(q, k, v, backend=backend)

    diffs = {
        name: float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32)
        )))
        for name, got, want in zip(
            ("out", "dq", "dk", "dv"), run(attend), run(reference_attention)
        )
    }
    check(
        max(diffs.values()) < FLASH_ATOL,
        f"{backend} attention at {shape} vs reference: {diffs}",
    )
    return diffs


# -- phase 2: the headline path ----------------------------------------------


def _producer_args(sizes: Sizes) -> list:
    return [
        "--shape", str(sizes.shape[0]), str(sizes.shape[1]),
        "--batch", str(sizes.batch), "--encoding", "tile",
        "--tile", "16", "32", "--tile-rgba",
        "--tile-capacity", sizes.tile_capacity,
        "--tile-pal-bits", sizes.tile_pal_bits,
        "--trace-every", "8",
    ]


def _recording_step(step, sink: list):
    """``step`` with every dispatch's K-vector of losses kept (device
    arrays, fetched after the drain) — the driver itself only ever
    fetches the last of each."""

    def recorded(state, batch):
        state, m = step(state, batch)
        sink.append(m["loss"])
        return state, m

    recorded._cache_size = step._cache_size  # keeps the retrace audit on
    return recorded


def _assert_native_producers(n_producers: int) -> None:
    """Parent and every producer loaded the g++-built rasterizer and
    tile scanner, not the Python fallback an order slower."""
    from blendjax._native import (
        load_render_frame,
        load_tile_delta_palidx,
        native_status,
    )
    from blendjax.obs.lineage import lineage

    load_render_frame()
    load_tile_delta_palidx()
    check(all(native_status().values()), f"native build: {native_status()}")
    report = lineage.report()
    check(len(report) == n_producers, f"producers seen: {sorted(report)}")
    for btid, entry in report.items():
        c = entry.get("telemetry", {}).get("counters", {})
        check(
            c.get("native.loaded", 0) >= 2 and not c.get("native.fallbacks"),
            f"producer {btid} runs the Python fallback: {c}",
        )


def _train_on_stream(it, pipe, model, loss_fn, steps: int, seed: int,
                     sizes: Sizes, label: str) -> dict:
    """Warm up, then ``steps`` driver steps of ``model`` off the live
    iterator ``it``; asserts the fused path's invariants."""
    import jax

    from blendjax.train import (
        TrainDriver,
        make_fused_tile_step,
        make_train_state,
    )
    from blendjax.utils.metrics import metrics

    tpu = on_tpu()
    metrics.reset()
    state = make_train_state(
        model, np.zeros((sizes.batch, *sizes.shape, 4), np.uint8),
        rng=jax.random.key(seed),
    )
    step = make_fused_tile_step(loss_fn=loss_fn)
    loss_vectors: list = []
    driver = TrainDriver(
        _recording_step(step, loss_vectors), state, inflight=2, sync_every=4
    )
    t0 = time.perf_counter()
    for _ in range(WARMUP_STEPS):
        batch = next(it)
        driver.submit(batch)
    driver.drain()
    compile_s = time.perf_counter() - t0
    traced = sorted(decode_paths())
    check(
        traced == ["pallas_spatial" if tpu else "xla_scatter"],
        f"{label}: decode traced through {traced}",
    )
    lowered = lower_fused(step, driver.state, batch).as_text()
    check(
        ("tpu_custom_call" in lowered) == tpu,
        f"{label}: decode kernel in the lowered step: "
        f"{'tpu_custom_call' in lowered}",
    )
    metrics.reset()
    compiled_before = step._cache_size()
    d0, s0 = driver.dispatches, driver.steps
    images = 0
    t0 = time.perf_counter()
    while driver.steps - s0 < steps:
        batch = next(it)
        check("_packed" in batch, f"{label}: pipeline yielded a decoded batch")
        images += driver._batch_images(batch)
        driver.submit(batch)
    final = driver.drain()
    seconds = time.perf_counter() - t0
    report = metrics.report()
    verdict = pipe.doctor(driver).render()
    losses = np.concatenate(
        [np.asarray(v, np.float32).reshape(-1) for v in loss_vectors]
    )
    check(np.isfinite(losses).all(), f"{label}: non-finite loss")
    check(
        driver.dispatches - d0 == driver.steps - s0 == steps,
        f"{label}: {driver.dispatches - d0} dispatches for "
        f"{driver.steps - s0} steps",
    )
    c, spans = report["counters"], report["spans"]
    check(not c.get("wire.seq_gaps"), f"{label}: wire.seq_gaps {c}")
    check("decode.dispatch" not in spans, f"{label}: standalone decode ran")
    check(not c.get("train.aot_fallbacks"), f"{label}: aot fallbacks")
    check(
        step._cache_size() == compiled_before and not c.get("device.retraces"),
        f"{label}: compiled after warm-up "
        f"({compiled_before} -> {step._cache_size()} programs)",
    )
    return {
        "model": label, "decode_path": traced[0],
        "compile_and_warmup_s": round(compile_s, 2),
        "steps": steps, "updates": int(losses.size), "images": images,
        "seconds": round(seconds, 3),
        "first_loss": float(losses[0]), "final_loss": float(final),
        "peak_bytes_in_use": peak_bytes_in_use(),
        "doctor": verdict,
    }


def former_loss(state, params, batch):
    """The StreamFormer's 16 outputs held to the cube's 8 corners."""
    from blendjax.train import corner_loss

    pred = state.apply_fn({"params": params}, batch["image"])
    return corner_loss(
        pred.reshape(-1, 8, 2), batch["xy"],
        image_shape=batch["image"].shape[1:3],
    )


def phase_headline(sizes: Sizes, seed: int) -> dict:
    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.models import CubeRegressor, StreamFormer
    from blendjax.obs.lineage import lineage

    lineage.reset()
    out: dict = {"phase": "headline", "producers": sizes.producers}
    with PythonProducerLauncher(
        script=CUBE_PRODUCER, num_instances=sizes.producers,
        named_sockets=["DATA"], seed=seed, proto="ipc",
        instance_args=[_producer_args(sizes)] * sizes.producers,
    ) as launcher, StreamDataPipeline(
        launcher.addresses["DATA"], batch_size=sizes.batch,
        chunk=sizes.chunk, emit_packed=True, launcher=launcher,
        timeoutms=60_000,
    ) as pipe:
        it = iter(pipe)
        cnn = _train_on_stream(
            it, pipe, CubeRegressor(), None, sizes.cnn_steps, seed, sizes,
            "CubeRegressor",
        )
        check(
            cnn["final_loss"] < cnn["first_loss"],
            f"CNN loss did not fall over {cnn['updates']} updates: "
            f"{cnn['first_loss']} -> {cnn['final_loss']}",
        )
        out["cnn"] = cnn
        _assert_native_producers(sizes.producers)
        out["streamformer"] = _train_on_stream(
            it, pipe, StreamFormer(**sizes.former), former_loss,
            sizes.former_steps, seed, sizes, "StreamFormer",
        )
    return out


# -- phase 3: the other loop --------------------------------------------------


def phase_rl(sizes: Sizes, seed: int) -> dict:
    from blendjax.env import BatchedRemoteEnv
    from blendjax.models import QNetwork
    from blendjax.rl import (
        ActorPool,
        HostQPolicy,
        RLTrainDriver,
        TrajectoryReservoir,
        make_dqn_step,
        make_rl_train_state,
    )

    batch = 32
    reservoir = TrajectoryReservoir(512, rng=seed, prioritized=True)
    model = QNetwork(hidden=(32, 32), n_actions=3)
    state = make_rl_train_state(
        model, np.zeros((1, 4), np.float32), learning_rate=1e-3
    )
    step = make_dqn_step(reservoir, model.apply, gamma=0.98)
    with BatchedRemoteEnv(
        script=CARTPOLE_PRODUCER, num_envs=2, seed=seed
    ) as venv:
        pool = ActorPool(
            venv, reservoir, HostQPolicy(3, eps_steps=1500, seed=seed),
            action_map=np.array([-2.0, 0.0, 2.0], np.float32),
        )
        driver = RLTrainDriver(
            step, state, reservoir, actors=pool, batch_size=batch,
            min_fill=2 * batch, sync_every=4, inflight=2,
        )
        with pool:
            while driver.steps < sizes.rl_steps:
                driver.train_step()
            loss = driver.drain()
    check(np.isfinite(loss), f"DQN loss {loss}")
    check(
        driver.dispatches == driver.steps == sizes.rl_steps,
        f"rl: {driver.dispatches} dispatches for {driver.steps} steps",
    )
    check(
        pool.env_steps == reservoir.inserts,
        f"rl: {pool.env_steps} env steps, {reservoir.inserts} inserted",
    )
    return {
        "phase": "rl", "learner_steps": driver.steps, "final_loss": loss,
        "env_steps": pool.env_steps, "episodes": pool.episodes,
    }


# -- four chips: the mesh path against one device ----------------------------


def _record_stream(sizes: Sizes, seed: int, prefix: str) -> None:
    """One producer's first ``mesh_batches`` tile messages (the
    reference frame rides the first), teed raw to ``<prefix>_00.bjr``."""
    from blendjax.data.stream import RemoteStream
    from blendjax.launcher import PythonProducerLauncher

    with PythonProducerLauncher(
        script=CUBE_PRODUCER, num_instances=1, named_sockets=["DATA"],
        seed=seed, proto="ipc", instance_args=[_producer_args(sizes)],
    ) as launcher:
        stream = RemoteStream(
            launcher.addresses["DATA"], timeoutms=60_000,
            max_items=sizes.mesh_batches, record_path_prefix=prefix,
        )
        n = sum(1 for _ in stream)
    check(n == sizes.mesh_batches, f"recorded {n} messages")


def _mesh_leg(sizes: Sizes, seed: int, prefix: str, layout, devices,
              fused: bool) -> dict:
    """The recording trained through ``MeshTrainDriver.build`` under
    ``layout`` on ``devices``: every dispatch's f32 loss, and what the
    run showed about placement, collectives and the decode path."""
    import jax
    import jax.numpy as jnp

    from blendjax.data import StreamDataPipeline
    from blendjax.models import CubeRegressor
    from blendjax.parallel import create_mesh
    from blendjax.parallel.sharding import resolve_layout
    from blendjax.train import MeshTrainDriver
    from blendjax.utils.metrics import metrics

    metrics.reset()
    mesh = create_mesh(resolve_layout(layout).mesh_axes(), devices=devices)
    model = CubeRegressor(dtype=jnp.float32)
    driver = MeshTrainDriver.build(
        model, mesh, np.zeros((sizes.batch, *sizes.shape, 4), np.uint8),
        fused=fused, layout=layout, rng=jax.random.key(seed),
        inflight=2, sync_every=1,  # every dispatch's loss lands in .losses
    )
    batch_devices = set()
    hlo = ""  # compiled text of the mesh step; the one-device leg needs none
    with StreamDataPipeline.from_recording(
        prefix, batch_size=sizes.batch, mesh=mesh,
        chunk=sizes.mesh_chunk if fused else 1, emit_packed=fused,
    ) as pipe:
        for batch in pipe:
            if len(devices) > 1 and not hlo:
                hlo = _compiled_text(driver.step, driver.state, batch, fused)
            if not fused:
                batch_devices |= {
                    s.device for s in batch["image"].addressable_shards
                }
            driver.submit(batch)
    driver.drain()
    losses = np.asarray(driver.losses, np.float32)
    param_devices = set()
    sharded_params = 0
    for leaf in jax.tree_util.tree_leaves(driver.state.params):
        param_devices |= {s.device for s in leaf.addressable_shards}
        sharded_params += not leaf.sharding.is_fully_replicated
    return {
        "layout": driver.layout, "fused": fused, "devices": len(devices),
        "losses": losses,
        "batch_devices": len(batch_devices),
        "param_devices": len(param_devices),
        "sharded_params": int(sharded_params),
        "all_reduce": "all-reduce" in hlo, "all_gather": "all-gather" in hlo,
        "kernel_in_hlo": "tpu_custom_call" in hlo,
        "decode_paths": decode_paths(),
        "decode_dispatches": metrics.report()["spans"].get(
            "decode.dispatch", {}
        ).get("count", 0),
        "dispatches": driver.dispatches, "steps": driver.steps,
    }


def _compiled_text(step, state, batch, fused: bool) -> str:
    """Optimized HLO of the program this leg dispatches (collectives are
    put in by the compiler, so the lowered text does not show them)."""
    if fused:
        lowered = lower_fused(step, state, batch)
    else:
        fields = {
            k: v for k, v in batch.items()
            if not k.startswith("_") and getattr(v, "ndim", 0) >= 1
        }
        lowered = step.lower(state, fields)
    return lowered.compile().as_text()


def _check_mesh_leg(leg: dict, ref: dict, steps: int) -> None:
    """A mesh leg against its one-device twin and against what a run on
    four chips has to show."""
    what = f"{leg['layout']} fused={leg['fused']}"
    tpu = on_tpu()
    diff = float(np.max(np.abs(leg["losses"] - ref["losses"])))
    leg["max_loss_diff_vs_one_device"] = diff
    check(
        leg["steps"] == ref["steps"] == steps == len(leg["losses"]),
        f"{what}: {leg['steps']} steps, {len(leg['losses'])} losses",
    )
    check(diff <= F32_EXACT_ATOL, f"{what}: loss diff {diff}")
    check(leg["dispatches"] == leg["steps"], f"{what}: dispatches != steps")
    check(leg["all_reduce"], f"{what}: no all-reduce compiled")
    check(
        leg["param_devices"] == 4,
        f"{what}: params on {leg['param_devices']} devices",
    )
    if "fsdp" in leg["layout"]:
        check(leg["all_gather"], f"{what}: no all-gather compiled")
        check(leg["sharded_params"] > 0, f"{what}: no param sharded")
    if not leg["fused"]:  # fused: the batch arrives as replicated bytes
        check(
            leg["batch_devices"] == 4,
            f"{what}: batch on {leg['batch_devices']} devices",
        )
    paths = leg["decode_paths"]
    check(
        bool(paths.get("shard_map")) == bool(paths.get("pallas_spatial")) == tpu,
        f"{what}: decode paths {paths}",
    )


def phase_four_chips(sizes: Sizes, seed: int) -> dict:
    """One device vs ``data`` (fused and decode-then-step) vs
    ``data2xfsdp2`` on the same recorded stream. f32 twins, matmul and
    convolution precision pinned to "highest" around the comparison: at
    the chip's default an f32 convolution is computed in bf16 passes and
    the CPU-set bar would measure that, not the sharding."""
    import jax

    devices = jax.devices()
    check(len(devices) >= 4, f"needs 4 devices, found {len(devices)}")
    devices = devices[:4]
    out: dict = {"phase": "four_chips", "bar": F32_EXACT_ATOL, "legs": []}
    with tempfile.TemporaryDirectory() as tmp, \
            jax.default_matmul_precision("highest"):
        prefix = os.path.join(tmp, "stream")
        _record_stream(sizes, seed, prefix)
        for fused in (True, False):
            steps = sizes.mesh_batches // (sizes.mesh_chunk if fused else 1)
            ref = _mesh_leg(sizes, seed, prefix, "data1", devices[:1], fused)
            for layout in ["data4"] if fused else ["data4", "data2xfsdp2"]:
                leg = _mesh_leg(sizes, seed, prefix, layout, devices, fused)
                _check_mesh_leg(leg, ref, steps)
                losses = leg.pop("losses")
                leg["first_loss"], leg["final_loss"] = (
                    float(losses[0]), float(losses[-1])
                )
                out["legs"].append(leg)
    # the fused attention core per shard: the benchmark's shape a chip,
    # batch-sharded, the mesh declared as the step builders declare it
    from blendjax.parallel import create_mesh

    before = counters().get("attn.path.shard_map", 0)
    b, *rest = sizes.attn_shape
    out["attn_max_abs_diff"] = _attention_diffs(
        np.random.default_rng(seed), (4 * b, *rest),
        "auto" if on_tpu() else "flash",
        mesh=create_mesh({"data": 4}, devices=devices),
    )
    check(
        counters().get("attn.path.shard_map", 0) == before + 1,
        "attention did not run per shard",
    )
    return out


# -- main ----------------------------------------------------------------------


def run(phases, sizes: Sizes, seed: int) -> bool:
    ok = True
    for phase in phases:
        t0 = time.perf_counter()
        try:
            line = phase(sizes, seed)
            line["ok"] = True
        except Exception as e:  # reported, and the run fails
            import traceback

            traceback.print_exc()
            line = {"phase": phase.__name__, "ok": False, "error": repr(e)[:400]}
            ok = False
        line["phase_seconds"] = round(time.perf_counter() - t0, 2)
        emit(line)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the 2x2 mesh path and its one-device comparison",
    )
    args = ap.parse_args(argv)

    import jax

    from blendjax.obs.devledger import chip_peak_flops
    from blendjax.train import configure_compilation_cache

    device = device_record()
    if device["platform"] != "tpu":
        print(json.dumps({
            "ok": False, "error": "no TPU: nothing was run", "device": device,
        }), file=sys.stderr)
        return 2
    chip_peak_flops(device["kind"])  # a chip without a peak on record is an error
    cache_dir = configure_compilation_cache()
    cache_events = {"hits": 0, "misses": 0}

    def count_cache_event(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            cache_events[event.rsplit("_", 1)[1]] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    emit({
        "phase": "start", "jax": jax.__version__, "device": device,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": len(os.listdir(cache_dir)),
    })
    phases = (
        [phase_four_chips] if args.four_chips
        else [phase_kernels, phase_headline, phase_rl]
    )
    t0 = time.perf_counter()
    ok = run(phases, REAL, args.seed)
    emit({
        "phase": "end", "seconds": round(time.perf_counter() - t0, 1),
        "compile_cache": cache_events,
    })
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    from blendjax.launcher.launcher import kill_all_spawned

    try:
        code = main()
    finally:
        kill_all_spawned()  # no child outlives the smoke, whatever raised
    sys.exit(code)
