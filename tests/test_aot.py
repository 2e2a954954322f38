"""Instant-start tests: AOT step compilation + the persistent cache.

The acceptance contract: an AOT-dispatched step must be *bitwise*
equal to the plain jit path for every signature in the bucket ladder,
unseen shapes must fall back (counted) rather than fail, the keyed
manifest must read warm-vs-cold correctly, and ``TrainDriver.build``
must stamp the startup clocks the ``live_start`` bench row reports.
"""

import os

import numpy as np
import optax
import pytest

from blendjax.data import bucket_sizes, pad_to_bucket
from blendjax.models import CubeRegressor
from blendjax.train import (
    TrainDriver,
    make_supervised_step,
    make_train_state,
)
from blendjax.train.aot import (
    AotStepSet,
    batch_specs_for_ladder,
    build_aot_step,
    cache_key,
)
from blendjax.utils.metrics import metrics

B, HW = 8, 16


@pytest.fixture(autouse=True)
def _clean_metrics():
    metrics.reset()
    yield
    metrics.reset()


def _counters():
    return metrics.report()["counters"]


def _batch(n=B, seed=7):
    rng = np.random.default_rng(seed)
    return {
        "image": rng.integers(0, 255, (n, HW, HW, 4), np.uint8),
        "xy": (rng.random((n, 8, 2)) * HW).astype(np.float32),
    }


def _state(batch):
    return make_train_state(
        CubeRegressor(), batch["image"], optimizer=optax.sgd(0.01)
    )


# -- ladder derivation --------------------------------------------------------


def test_batch_specs_cover_full_batch_and_masked_ladder():
    specs = batch_specs_for_ladder(_batch())
    # steady state first: full batch, no mask
    assert "_mask" not in specs[0]
    assert specs[0]["image"].shape == (B, HW, HW, 4)
    assert specs[0]["xy"].dtype == np.float32
    # then every pad_to_bucket size, each with its f32 mask
    ladder = [s["image"].shape[0] for s in specs[1:]]
    assert tuple(ladder) == bucket_sizes(B)
    for s in specs[1:]:
        assert s["_mask"].dtype == np.float32
        assert s["_mask"].shape == (s["image"].shape[0],)


def test_batch_specs_ignore_stamps_and_scalars():
    batch = {**_batch(), "_seq": 3, "frameid": 9, "_trace": {"t": 1}}
    specs = batch_specs_for_ladder(batch)
    assert set(specs[0]) == {"image", "xy"}


def test_batch_specs_honor_explicit_buckets():
    specs = batch_specs_for_ladder(_batch(), buckets=(2, 8))
    assert [s["image"].shape[0] for s in specs] == [B, 2, 8]


def test_batch_specs_require_array_fields():
    with pytest.raises(ValueError):
        batch_specs_for_ladder({"frameid": 3, "_seq": 0})


def test_batch_specs_carry_committed_sharding():
    """A mesh run's example batch arrives sharded over the data axis;
    the ladder specs must keep that sharding — an executable lowered
    against a replicated batch is a DIFFERENT program (no grad-sync
    collectives) and rejects the live sharded layout at dispatch."""
    import jax

    from blendjax.parallel import batch_sharding, create_mesh

    import numpy as _np

    mesh = create_mesh({"data": -1})  # conftest forces 8 CPU devices
    sharded = {
        k: jax.device_put(v, batch_sharding(mesh))
        for k, v in _batch().items()
    }
    n_dev = int(_np.prod(tuple(mesh.devices.shape)))
    specs = batch_specs_for_ladder(sharded, buckets=(B, 4))
    assert specs[0]["image"].sharding == sharded["image"].sharding
    # a bucket the mesh still divides keeps the sharding (B == lead)
    assert specs[1]["image"].sharding == sharded["image"].sharding
    # a bucket the mesh can NOT divide (4 over 8 devices) drops it
    # rather than compiling an executable no real batch could feed
    if 4 % n_dev:
        assert specs[2]["image"].sharding is None
    # numpy example batches lower exactly as before: no sharding
    plain = batch_specs_for_ladder(_batch(), buckets=(4,))
    assert plain[0]["image"].sharding is None


# -- AOT-vs-eager equality ----------------------------------------------------


def test_aot_vs_eager_bitwise_loss_equality_across_ladder():
    """Every dispatchable signature — the full batch plus each padded
    bucket — must produce the identical f32 loss and identical params
    through the precompiled executable and the plain jit."""
    full = _batch()
    state = _state(full)
    aot = build_aot_step(make_supervised_step(donate=False), state, full)
    ref_step = make_supervised_step(donate=False)

    cases = [dict(full)]
    for n in (1, 2, 3, 5, 7):
        cases.append(pad_to_bucket(
            {"image": full["image"][:n], "xy": full["xy"][:n],
             "_partial": True},
            batch_size=B,
        ))

    for batch in cases:
        s_aot, m_aot = aot(state, dict(batch))
        s_ref, m_ref = ref_step(
            state, {k: v for k, v in batch.items()
                    if k == "_mask" or not k.startswith("_")},
        )
        assert float(m_aot["loss"]) == float(m_ref["loss"])  # bitwise
        import jax

        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            s_aot.params, s_ref.params,
        )
    # the whole ladder dispatched through precompiled executables
    assert _counters().get("train.aot_fallbacks") is None
    assert len(aot.signatures) == 1 + len(bucket_sizes(B))


def test_aot_unseen_shape_falls_back_and_counts():
    full = _batch()
    state = _state(full)
    aot = build_aot_step(make_supervised_step(donate=False), state, full)
    odd = _batch(n=3)  # lead 3, unmasked: not a ladder signature
    _, m = aot(state, odd)
    assert np.isfinite(float(m["loss"]))
    assert _counters().get("train.aot_fallbacks") == 1


def test_aot_compile_span_recorded():
    full = _batch()
    state = _state(full)
    build_aot_step(make_supervised_step(donate=False), state, full,
                   buckets=(8,))
    spans = metrics.report()["spans"]
    assert spans["train.compile_ms"]["count"] == 1
    assert spans["train.compile_ms"]["total_s"] > 0


# -- persistent cache manifest ------------------------------------------------


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.usefixtures("compile_cache_config_guard")
@pytest.mark.parametrize(
    "env, explicit, expect",
    [
        ("env", "explicit", "env"),    # the environment wins over the caller
        ("env", None, "env"),
        (None, "explicit", "explicit"),
        (None, None, os.path.join(_CHECKOUT, ".xla_cache")),
    ],
)
def test_cache_dir_resolution(monkeypatch, tmp_path, env, explicit, expect):
    """One resolver: JAX_COMPILATION_CACHE_DIR, else the caller's
    directory, else <checkout>/.xla_cache — and with the variable set no
    other directory is set or created."""
    import jax

    from blendjax.train import configure_compilation_cache

    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = configure_compilation_cache(
        str(tmp_path / explicit) if explicit else None
    )
    want = expect if os.path.isabs(expect) else str(tmp_path / expect)
    assert got == want == jax.config.jax_compilation_cache_dir
    if env and explicit:
        assert not (tmp_path / explicit).exists()


@pytest.mark.usefixtures("compile_cache_config_guard")
def test_aot_build_keeps_xla_cache_where_env_says(monkeypatch, tmp_path):
    """aot_cache_dir names the manifest's home only: with the variable
    set, XLA's entries go to the environment's directory."""
    import jax

    env_dir = tmp_path / "env"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    full = _batch()
    build_aot_step(make_supervised_step(donate=False), _state(full), full,
                   buckets=(8,), cache_dir=str(tmp_path / "aot"), key="k")
    assert jax.config.jax_compilation_cache_dir == str(env_dir)
    assert os.listdir(tmp_path / "aot") == ["aot_manifest.json"]
    assert os.listdir(env_dir)


@pytest.mark.usefixtures("compile_cache_config_guard")
def test_manifest_cold_then_warm_counters(tmp_path):
    cache = str(tmp_path / "xla-cache")
    full = _batch()
    state = _state(full)
    key = cache_key(model=CubeRegressor(), buckets=(8,))

    cold = build_aot_step(make_supervised_step(donate=False), state, full,
                          buckets=(8,), cache_dir=cache, key=key)
    assert cold.cache_misses == 2 and cold.cache_hits == 0

    warm = build_aot_step(make_supervised_step(donate=False), state, full,
                          buckets=(8,), cache_dir=cache, key=key)
    assert warm.cache_hits == 2 and warm.cache_misses == 0
    c = _counters()
    assert c.get("train.aot_cache_hits") == 2
    assert c.get("train.aot_cache_misses") == 2
    assert os.path.exists(os.path.join(cache, "aot_manifest.json"))


@pytest.mark.usefixtures("compile_cache_config_guard")
def test_manifest_key_isolation(tmp_path):
    """A different cache key (different model/ladder/mesh) never reads
    another key's manifest entries as warm."""
    cache = str(tmp_path / "xla-cache")
    full = _batch()
    state = _state(full)
    build_aot_step(make_supervised_step(donate=False), state, full,
                   buckets=(8,), cache_dir=cache, key="key-a")
    other = build_aot_step(make_supervised_step(donate=False), state, full,
                           buckets=(8,), cache_dir=cache, key="key-b")
    assert other.cache_misses == 2 and other.cache_hits == 0


def test_cache_key_anatomy():
    base = cache_key(model=CubeRegressor(), buckets=(1, 2, 4, 8))
    assert base == cache_key(model=CubeRegressor(), buckets=(1, 2, 4, 8))
    assert base != cache_key(model=CubeRegressor(), buckets=(8,))
    assert base != cache_key(model="other.Model", buckets=(1, 2, 4, 8))
    assert base != cache_key(model=CubeRegressor(), buckets=(1, 2, 4, 8),
                             precision="bf16")


# -- TrainDriver.build integration --------------------------------------------


def test_train_driver_build_stamps_startup_clocks():
    full = _batch()
    drv = TrainDriver.build(
        CubeRegressor(), full, optimizer=optax.sgd(0.01),
        inflight=2, sync_every=0, buckets=(8,),
    )
    assert isinstance(drv.step, AotStepSet)
    assert drv.startup_ms is not None and drv.startup_ms > 0
    assert drv.time_to_first_step_ms is None  # nothing retired yet
    for _ in range(3):
        drv.submit(dict(full))
    _, final = drv.finish()
    assert np.isfinite(final)
    stats = drv.stats
    assert stats["startup_ms"] == drv.startup_ms
    assert stats["time_to_first_step_ms"] is not None
    assert stats["time_to_first_step_ms"] >= 0
    assert _counters().get("train.aot_fallbacks") is None


def test_train_driver_build_requires_batch_dict():
    with pytest.raises(TypeError):
        TrainDriver.build(CubeRegressor(), np.zeros((8, HW, HW, 4), np.uint8))


def test_train_driver_build_resume_restores_state_and_counters(tmp_path):
    from blendjax.checkpoint import SnapshotManager

    full = _batch()
    with SnapshotManager(str(tmp_path), keep=2) as mgr:
        drv = TrainDriver.build(
            CubeRegressor(), full, optimizer=optax.sgd(0.01),
            inflight=2, sync_every=0, buckets=(8,),
        )
        for _ in range(4):
            drv.submit(dict(full))
        state, _ = drv.finish()
        mgr.save(4, state, session={"driver": drv.state_dict()})

    with SnapshotManager(str(tmp_path), keep=2) as mgr:
        resumed = TrainDriver.build(
            CubeRegressor(), full, optimizer=optax.sgd(0.01),
            inflight=2, sync_every=0, buckets=(8,),
            checkpoint=mgr, resume=True,
        )
        assert int(resumed.state.step) == 4
        assert resumed.resumed_session is not None
        assert resumed.startup_ms is not None
        # resumed driver keeps stepping through the warmed AOT set
        resumed.submit(dict(full))
        state, _ = resumed.finish()
        assert int(state.step) == 5
