"""blendjax.checkpoint: async sharded snapshots, pickle-free session
state, preemption wiring — plus coverage for the orbax-backed
``blendjax.train.CheckpointManager`` wrapper (ISSUE 12).

The resume-equality acceptance contract (kill -9 -> resume ->
identical f32 trajectory, single-chip AND mesh, incl. elastic 8->4)
lives in ``tests/test_resume.py``; this file pins the building blocks:
format roundtrips, shard-walking saves, clone-before-donate safety,
bitwise-continuable session state per component, and the watchdog /
SIGTERM arms.
"""

import json
import os
import signal
import sys
import threading
import time

import numpy as np
import pytest

import jax

from blendjax.checkpoint import (
    PreemptionGuard,
    PreemptionRequested,
    SnapshotManager,
    collect_session,
    pack_session,
    restore_session,
    unpack_session,
)
from blendjax.models import CubeRegressor
from blendjax.parallel import batch_sharding, create_mesh
from blendjax.train import TrainDriver, make_supervised_step, make_train_state
from blendjax.utils.metrics import metrics as reg

B = 8
HW = 16


def _mesh(n):
    return create_mesh({"data": n}, devices=jax.devices()[:n])


def _batches(n, seed=0, batch=B):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        yield {
            "image": rng.integers(0, 255, (batch, HW, HW, 4), np.uint8),
            "xy": (rng.random((batch, 8, 2)) * HW).astype(np.float32),
        }


def _state(mesh=None):
    return make_train_state(
        CubeRegressor(features=(8,)), np.zeros((B, HW, HW, 4), np.uint8),
        mesh=mesh,
    )


# -- session codec ------------------------------------------------------------


def test_session_codec_roundtrip():
    doc = {
        "arr": np.arange(12, dtype=np.float32).reshape(3, 4),
        "flags": np.array([True, False]),
        "big": 2**100,  # PCG64 state words are 128-bit
        "neg_big": -(2**80),
        "rng": np.random.default_rng(3).bit_generator.state,
        "nested": {"l": [1, 2.5, "x", None, b"raw"], 7: "int-key"},
    }
    out = unpack_session(pack_session(doc))
    assert np.array_equal(out["arr"], doc["arr"])
    assert out["arr"].dtype == np.float32
    assert np.array_equal(out["flags"], doc["flags"])
    assert out["big"] == 2**100 and out["neg_big"] == -(2**80)
    assert out["nested"]["l"] == [1, 2.5, "x", None, b"raw"]
    assert out["nested"][7] == "int-key"
    # the decoded rng state actually drives a Generator
    g = np.random.default_rng(0)
    g.bit_generator.state = out["rng"]
    ref = np.random.default_rng(3)
    assert g.random() == ref.random()


def test_session_codec_is_pickle_free():
    class Opaque:
        pass

    with pytest.raises(TypeError, match="pickle"):
        pack_session({"bad": Opaque()})
    with pytest.raises(ValueError, match="reserved"):
        pack_session({"__nd__": 1})
    with pytest.raises(TypeError, match="object dtype"):
        pack_session({"o": np.array([object()])})


# -- snapshot manager ---------------------------------------------------------


def test_snapshot_roundtrip_walks_shards_and_preserves_shardings(tmp_path):
    mesh = _mesh(8)
    sharded = jax.device_put(
        np.arange(64, dtype=np.float32).reshape(8, 8),
        batch_sharding(mesh),
    )
    replicated = jax.device_put(
        np.ones((3,), np.float32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    state = {"w": sharded, "b": replicated, "step": 4}
    with SnapshotManager(str(tmp_path), keep=3) as mgr:
        mgr.save_async(4, state)
        mgr.wait()
        assert mgr.steps() == [4]
        # per-addressable-shard writes: the data-sharded leaf wrote 8
        # shard files, the replicated one deduped to 1 (replica_id 0)
        with open(os.path.join(
            str(tmp_path), "step-00000004", "manifest.json"
        )) as f:
            manifest = json.load(f)
        shard_counts = {
            e["path"]: len(e.get("shards", []))
            for e in manifest["leaves"]
        }
        assert shard_counts["['w']"] == 8
        assert shard_counts["['b']"] == 1
        template = {
            "w": jax.device_put(np.zeros((8, 8), np.float32),
                                batch_sharding(mesh)),
            "b": jax.device_put(
                np.zeros((3,), np.float32),
                jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()
                ),
            ),
            "step": 0,
        }
        res = mgr.restore(template)
        assert res.step == 4 and res.state["step"] == 4
        assert np.array_equal(np.asarray(res.state["w"]),
                              np.asarray(sharded))
        assert res.state["w"].sharding == template["w"].sharding
        assert not res.resharded


def test_restore_on_empty_dir_returns_none(tmp_path):
    with SnapshotManager(str(tmp_path)) as mgr:
        assert mgr.restore(_state()) is None
        assert mgr.latest_step() is None


def test_elastic_restore_onto_smaller_mesh_counts_resharded(tmp_path):
    reg.reset()
    mesh8 = _mesh(8)
    state = {"ring": jax.device_put(
        np.arange(128, dtype=np.float32).reshape(8, 16),
        batch_sharding(mesh8),
    )}
    with SnapshotManager(str(tmp_path)) as mgr:
        mgr.save_async(1, state)
        mgr.wait()
        mesh4 = _mesh(4)
        template = {"ring": jax.device_put(
            np.zeros((8, 16), np.float32), batch_sharding(mesh4)
        )}
        res = mgr.restore(template)
    assert np.array_equal(np.asarray(res.state["ring"]),
                          np.arange(128, dtype=np.float32).reshape(8, 16))
    assert len(res.state["ring"].sharding.device_set) == 4
    assert res.resharded
    assert reg.report()["counters"]["ckpt.resharded_restores"] == 1


def test_async_save_survives_subsequent_donation(tmp_path):
    """The clone-before-donate contract: a snapshot taken between two
    steps restores the state AS OF the snapshot, even though the very
    next dispatch donated (and overwrote) the live buffers."""
    state = _state()
    step = make_supervised_step()
    batches = list(_batches(4, seed=1))
    state, _ = step(state, batches[0])
    ref = jax.tree.map(np.asarray, jax.device_get(state.params))
    with SnapshotManager(str(tmp_path)) as mgr:
        mgr.save_async(1, state)
        for b in batches[1:]:  # donate the live state repeatedly
            state, _ = step(state, b)
        mgr.wait()
        res = mgr.restore(_state())
    restored = jax.tree.map(np.asarray, jax.device_get(res.state.params))
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(restored)):
        assert np.array_equal(a, b)
    # and the live state did move on
    live = jax.tree.leaves(jax.device_get(state.params))
    assert not all(
        np.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(ref), live)
    )


def test_retention_prunes_and_tmp_sweep(tmp_path):
    state = {"w": np.ones((2,), np.float32)}
    with SnapshotManager(str(tmp_path), keep=2) as mgr:
        for s in range(1, 6):
            mgr.save_async(s, state)
            mgr.wait()
        assert mgr.steps() == [4, 5]
    # a kill -9 mid-write leaves a .tmp- stage; the next manager sweeps
    stale = tmp_path / ".tmp-00000009-123"
    stale.mkdir()
    (stale / "garbage.bin").write_bytes(b"x")
    mgr2 = SnapshotManager(str(tmp_path))
    assert not stale.exists()
    assert mgr2.steps() == [4, 5]
    mgr2.close()


def test_writer_backpressure_replaces_pending(tmp_path):
    """A slow disk degrades cadence, never accumulates device clones:
    the pending slot holds ONE snapshot and a newer save replaces it."""
    reg.reset()
    state = {"w": np.ones((2,), np.float32)}
    mgr = SnapshotManager(str(tmp_path))
    # stall the writer by holding its condition (re-entrant for this
    # thread) until the newer save has replaced the queued one: released
    # in between, a quick writer takes step 1 first and writes both
    with mgr._cv:
        mgr._pending = (1, state, {})
        mgr._ensure_thread()
        mgr.save_async(2, state)  # replaces queued step 1
    mgr.wait()
    assert mgr.steps() == [2]
    assert reg.report()["counters"]["ckpt.skipped"] == 1
    mgr.close()


# -- driver integration -------------------------------------------------------


def test_driver_checkpoint_cadence_keeps_one_dispatch_per_step(tmp_path):
    reg.reset()
    state = _state()
    drv = TrainDriver(
        make_supervised_step(), state, inflight=2, sync_every=1,
        checkpoint=SnapshotManager(str(tmp_path)), checkpoint_every=2,
        session_state=lambda: {"custom": {"mark": 1}},
    )
    for b in _batches(6, seed=2):
        drv.submit(b)
    drv.finish()
    drv.checkpoint.wait()
    report = reg.report()
    assert drv.checkpoints == 3
    # every cadence point was handed to the manager; a fast step loop
    # may legitimately outrun the writer, in which case the bounded
    # pending slot REPLACES a queued snapshot (ckpt.skipped) rather
    # than accumulating device clones — the newest cadence point
    # always commits
    committed = drv.checkpoint.steps()
    assert set(committed) <= {2, 4, 6} and committed[-1] == 6
    counters = report["counters"]
    assert counters["ckpt.saves"] + counters.get("ckpt.skipped", 0) == 3
    # the structural contract: checkpointing added ZERO train dispatches
    # and the save wall time landed on the writer thread's histogram
    assert report["spans"]["train.dispatch"]["count"] == 6
    assert report["histograms"]["ckpt.save_ms"]["count"] == len(committed)
    res = drv.checkpoint.restore(_state())
    assert res.session["custom"] == {"mark": 1}
    assert res.session["driver"]["steps"] == 6
    drv.checkpoint.close()


def test_request_checkpoint_lands_at_next_step_boundary(tmp_path):
    state = _state()
    drv = TrainDriver(
        make_supervised_step(), state, inflight=1, sync_every=0,
        checkpoint=SnapshotManager(str(tmp_path)), checkpoint_every=0,
    )
    batches = list(_batches(3, seed=3))
    drv.submit(batches[0])
    assert drv.checkpoints == 0  # no cadence configured
    drv.request_checkpoint()  # e.g. from the watchdog thread
    drv.submit(batches[1])
    assert drv.checkpoints == 1
    drv.submit(batches[2])
    assert drv.checkpoints == 1  # one request, one snapshot
    drv.finish()
    drv.checkpoint.wait()
    assert drv.checkpoint.steps() == [2]
    drv.checkpoint.close()


def test_driver_state_dict_roundtrip():
    state = _state()
    drv = TrainDriver(make_supervised_step(), state, sync_every=1)
    for b in _batches(3, seed=4):
        drv.submit(b)
    drv.finish()
    d = unpack_session(pack_session({"driver": drv.state_dict()}))
    drv2 = TrainDriver(make_supervised_step(), _state(), sync_every=1)
    drv2.load_state_dict(d["driver"])
    assert drv2.steps == drv.steps
    assert drv2.losses == drv.losses


# -- preemption ---------------------------------------------------------------


def test_sigterm_drains_snapshots_and_raises(tmp_path):
    state = _state()
    drv = TrainDriver(
        make_supervised_step(), state, inflight=2, sync_every=1,
        checkpoint=SnapshotManager(str(tmp_path)), checkpoint_every=0,
    )
    guard = PreemptionGuard(drv)
    try:
        batches = list(_batches(4, seed=5))
        drv.submit(batches[0])
        drv.submit(batches[1])
        os.kill(os.getpid(), signal.SIGTERM)
        # the handler only sets a flag; the drain + snapshot happen at
        # the next step boundary, where donated buffers have settled
        with pytest.raises(PreemptionRequested, match="committed"):
            drv.submit(batches[2])
    finally:
        guard.uninstall()
    drv.checkpoint.wait()
    assert drv.checkpoint.steps() == [2]
    res = drv.checkpoint.restore(_state())
    assert res.session["driver"]["steps"] == 2
    assert reg.counter_value("ckpt.preempt_signals") >= 1
    drv.checkpoint.close()


def test_preemption_guard_inert_off_main_thread():
    captured = {}

    def worker():
        captured["guard"] = PreemptionGuard(signals=(signal.SIGTERM,))

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    g = captured["guard"]
    assert g.installed is False
    g.request()  # programmatic preemption still works
    assert g.requested


def test_preempt_flush_reports_failed_snapshot(tmp_path):
    """The writer never raises into the train loop, so the preemption
    path must not report 'committed' on silence alone: a failed flush
    names the failure (the operator/scheduler would otherwise believe
    steps were preserved that are gone)."""
    state = _state()
    mgr = SnapshotManager(str(tmp_path))

    def boom(step, st, session):
        raise OSError(28, "No space left on device")

    mgr._write_one = boom
    drv = TrainDriver(
        make_supervised_step(), state, inflight=1, sync_every=1,
        checkpoint=mgr,
    )
    guard = PreemptionGuard(drv)
    try:
        batches = list(_batches(2, seed=8))
        drv.submit(batches[0])
        guard.request()
        with pytest.raises(PreemptionRequested, match="FAILED"):
            drv.submit(batches[1])
    finally:
        guard.uninstall()
    with pytest.raises(RuntimeError, match="write failed"):
        drv.checkpoint_now()
    mgr.close()


def test_driver_state_dict_bounds_loss_tail():
    drv = TrainDriver(make_supervised_step(), _state())
    drv.losses = [float(i) for i in range(drv.LOSS_TAIL + 100)]
    drv.steps = drv.dispatches = len(drv.losses)
    d = drv.state_dict()
    assert len(d["losses"]) == drv.LOSS_TAIL
    assert d["losses_total"] == drv.LOSS_TAIL + 100
    assert d["losses"][-1] == drv.losses[-1]


# -- component session state --------------------------------------------------


def _echo_batches(n, seed=0, batch=4):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        yield {
            "image": rng.integers(0, 255, (batch, HW, HW, 4), np.uint8),
            "xy": (rng.random((batch, 8, 2)) * HW).astype(np.float32),
        }


def test_echo_session_state_is_bitwise_continuable():
    """The headline determinism contract: a restored echo pipeline
    draws the SAME slots with the SAME augmentation keys the
    uninterrupted run would have — byte-identical batches."""
    from blendjax.data.echo import EchoingPipeline

    a = EchoingPipeline(
        list(_echo_batches(4, seed=9)), capacity=16, max_echo_factor=6,
        batch_size=4, rng=5,
    )
    it = iter(a)
    drawn = 0
    # consume until the inner stream is fully inserted (the _DONE
    # sentinel popped), so the snapshot and continuation see no
    # further insert timing
    deadline = time.monotonic() + 10
    while not (a._inner_done and a._queue.empty()):
        next(it)
        drawn += 1
        assert time.monotonic() < deadline
    sd_raw = a.state_dict()
    # the snapshot must be copies, not references: the draw loop keeps
    # mutating slot accounting while the writer thread serializes
    use_at_snapshot = sd_raw["use"].copy()
    sd = unpack_session(pack_session({"echo": sd_raw}))["echo"]
    cont = [next(it) for _ in range(3)]
    assert np.array_equal(sd_raw["use"], use_at_snapshot)

    b = EchoingPipeline(
        iter(()), capacity=16, max_echo_factor=6, batch_size=4, rng=5,
    )
    b.load_state_dict(sd)
    itb = iter(b)
    resumed = [next(itb) for _ in range(3)]
    for x, y in zip(cont, resumed):
        for k in ("image", "xy"):
            assert np.array_equal(np.asarray(x[k]), np.asarray(y[k]))
    assert b.steps == a.steps and b.fresh == a.fresh
    a.stop()
    b.stop()


def test_reservoir_state_dict_preserves_ring_and_counters():
    from blendjax.data.echo import SampleReservoir

    r = SampleReservoir(8, augment=None, rng=1)
    r.insert({"x": np.arange(12, dtype=np.float32).reshape(6, 2)})
    r.sample(np.array([0, 1]))
    sd = unpack_session(pack_session(r.state_dict()))
    r2 = SampleReservoir(8, augment=None, rng=1)
    r2.load_state_dict(sd)
    assert r2.size == r.size and r2._draws == r._draws
    assert np.array_equal(
        np.asarray(r2.gather(np.arange(6))["x"]),
        np.asarray(r.gather(np.arange(6))["x"]),
    )
    # the cursor continues: the next insert lands in the same slots
    s1 = r.insert({"x": np.ones((4, 2), np.float32)})
    s2 = r2.insert({"x": np.ones((4, 2), np.float32)})
    assert np.array_equal(s1, s2)
    with pytest.raises(ValueError, match="capacity"):
        SampleReservoir(4).load_state_dict(sd)


def test_scenario_ledger_roundtrip_preserves_windows_and_theta():
    from blendjax.scenario import ScenarioSpace
    from blendjax.scenario.accounting import ScenarioAccounting

    space = ScenarioSpace.parse("easy:half_extent=u(0.8,1.2) / "
                                "hard:xy_jitter=g(2,0.5)")
    led = ScenarioAccounting()
    led.declare(space)
    stamps = (
        [{"id": "easy", "ver": 1}] * 3
        + [{"id": "hard", "ver": 1, "theta": [1.5]}] * 2
        + [{"id": "hard", "ver": 2, "theta": [2.5]}]
    )
    led.observe_rows(stamps, fresh=[True] * 4 + [False] * 2)
    led.observe_loss(stamps, 0.25)
    sd = unpack_session(pack_session(led.state_dict()))
    led2 = ScenarioAccounting()
    led2.load_state_dict(sd)
    assert led2.totals() == led.totals()
    r1, r2 = led.report(), led2.report()
    assert r2["scenarios"]["hard"]["versions"] == {1: 2, 2: 1}
    assert r2["scenarios"]["easy"]["loss"]["count"] == 3
    assert r1["declared"] == r2["declared"]
    # the curriculum's evidence window survived the restart
    assert led2.window_losses(reset=False) == led.window_losses(
        reset=False
    )
    assert led2.theta_samples("hard", drain=False) == [
        ([1.5], 0.25), ([1.5], 0.25), ([2.5], 0.25)
    ]


def test_curriculum_roundtrip_restores_space_in_place():
    from blendjax.scenario import ScenarioCurriculum, ScenarioSpace
    from blendjax.scenario.accounting import ScenarioAccounting

    space = ScenarioSpace.parse(
        "easy:half_extent=u(0.8,1.2) / hard:xy_jitter=16"
    )
    led = ScenarioAccounting()
    cur = ScenarioCurriculum(
        space, ledger=led, every_steps=4, min_rows=2, adapt_params=False,
    )
    led.observe_rows([{"id": "easy", "ver": 1}] * 4
                     + [{"id": "hard", "ver": 1}] * 4)
    led.observe_loss([{"id": "easy", "ver": 1}] * 4, 0.1)
    led.observe_loss([{"id": "hard", "ver": 1}] * 4, 0.9)
    assert cur.update() is not None
    assert space.version == 2
    sd = unpack_session(pack_session(cur.state_dict()))

    space2 = ScenarioSpace.parse(
        "easy:half_extent=u(0.8,1.2) / hard:xy_jitter=16"
    )
    led2 = ScenarioAccounting()
    cur2 = ScenarioCurriculum(
        space2, ledger=led2, every_steps=4, min_rows=2,
        adapt_params=False,
    )
    cur2.load_state_dict(sd)
    # restored IN PLACE: same object, adapted weights, bumped version
    assert space2.version == 2
    assert space2.weights() == pytest.approx(space.weights())
    assert cur2.updates == 1 and led2.space_version == 2


def test_lineage_roundtrip_restart_is_not_a_gap_storm():
    from blendjax.obs.lineage import FrameLineage

    ln = FrameLineage()
    for seq in range(6):
        ln.ingest({"btid": 0, "_seq": seq, "_pub_wall": time.time()})
    sd = unpack_session(pack_session(ln.state_dict()))
    ln2 = FrameLineage()
    ln2.load_state_dict(sd)
    rep = ln2.report()["0"]
    assert rep["last_seq"] == 5 and rep["received"] == 6
    # consumer + producer restarted together: fresh numbering from 0
    # reads as a RESTART through the restored seq position, zero gaps
    ln2.ingest({"btid": 0, "_seq": 0, "_pub_wall": time.time()})
    rep = ln2.report()["0"]
    assert rep["restarts"] == 1 and rep["seq_gaps"] == 0
    # a producer that kept publishing while the consumer was down:
    # the missed frames are HONEST gaps against the restored position
    ln3 = FrameLineage()
    ln3.load_state_dict(sd)
    ln3.ingest({"btid": 0, "_seq": 9, "_pub_wall": time.time()})
    assert ln3.report()["0"]["seq_gaps"] == 3


def test_fleet_controller_state_roundtrip():
    from test_fleet import FakeConnector, FakeLauncher, FakeLineage

    from blendjax.fleet import FleetController, FleetPolicy

    ctrl = FleetController(
        FakeLauncher(3), FakeConnector(),
        policy=FleetPolicy(min_instances=1, max_instances=6),
        lineage=FakeLineage(),
    )
    ctrl.admit_remote("render-box", "tcp://127.0.0.1:9402")
    sd = unpack_session(pack_session(ctrl.state_dict()))
    assert sd == {
        "launched": 3, "remote": {"render-box": "tcp://127.0.0.1:9402"},
    }
    launcher2, conn2 = FakeLauncher(1), FakeConnector()
    ctrl2 = FleetController(
        launcher2, conn2,
        policy=FleetPolicy(min_instances=1, max_instances=6),
        lineage=FakeLineage(),
    )
    ctrl2.load_state_dict(sd)
    # grew back to the saved count and re-admitted the remote member
    assert launcher2.active_count() == 3
    assert ctrl2.remote == {"render-box": "tcp://127.0.0.1:9402"}
    assert "tcp://127.0.0.1:9402" in conn2.connected
    assert ctrl2.state()["instances"] == 4


def test_collect_and_restore_session_roundtrip():
    class Comp:
        def __init__(self):
            self.loaded = None

        def state_dict(self):
            return {"v": 7}

        def load_state_dict(self, d):
            self.loaded = d

    c = Comp()
    session = collect_session(comp=c, skipped=None,
                              stream={"consumed": 12})
    assert session["_version"] == 1
    out = unpack_session(pack_session(session))
    c2 = Comp()
    restored = restore_session(out, comp=c2)
    assert c2.loaded == {"v": 7} and restored == ["comp"]
    with pytest.raises(ValueError, match="no state for"):
        restore_session(out, strict=True, other=Comp())
    with pytest.raises(ValueError, match="newer"):
        restore_session({"_version": 99})


# -- watchdog arm -------------------------------------------------------------


def test_flight_recorder_checkpoint_on_breach_arm(tmp_path):
    from blendjax.obs.watchdog import FlightRecorder

    calls = []
    rec = FlightRecorder(
        str(tmp_path), checkpoint=lambda: calls.append(1) or {"ok": 1}
    )
    bundle = rec.dump(reason="test-breach")
    assert calls == [1]
    with open(os.path.join(bundle, "checkpoint.json")) as f:
        doc = json.load(f)
    assert doc["requested"] is True and doc["result"] == {"ok": 1}


def test_reporter_wires_checkpoint_on_breach(tmp_path):
    from blendjax.obs import StatsReporter

    drv_flag = []
    rep = StatsReporter(
        interval_s=60, slos=["gauge(test.always) >= 100"],
        flight_dir=str(tmp_path),
        checkpoint_on_breach=lambda: drv_flag.append(True),
    )
    reg.gauge("test.always", 1)  # breaches the floor immediately
    rep.tick()
    assert drv_flag == [True]
    bundles = [d for d in os.listdir(tmp_path) if d.startswith("flight-")]
    assert len(bundles) == 1
    assert os.path.exists(
        os.path.join(tmp_path, bundles[0], "checkpoint.json")
    )


# -- the orbax wrapper (optional extra) ---------------------------------------


def _has_orbax():
    try:
        import orbax.checkpoint  # noqa: F401

        return True
    except ImportError:
        return False


orbax_required = pytest.mark.skipif(
    not _has_orbax(), reason="orbax-checkpoint not installed (optional "
    "extra blendjax[orbax])",
)


def test_orbax_missing_raises_actionable_import_error(tmp_path,
                                                      monkeypatch):
    monkeypatch.setitem(sys.modules, "orbax", None)
    monkeypatch.setitem(sys.modules, "orbax.checkpoint", None)
    from blendjax.train import CheckpointManager

    with pytest.raises(ImportError, match=r"blendjax\[orbax\]"):
        CheckpointManager(str(tmp_path))


@orbax_required
def test_orbax_save_restore_roundtrip(tmp_path):
    from blendjax.train import CheckpointManager, make_train_state

    state = _state()
    step = make_supervised_step()
    state, _ = step(state, next(_batches(1, seed=6)))
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    mgr.save(1, state)
    mgr.wait()
    restored = mgr.restore(_state())
    assert restored is not None
    for a, b in zip(
        jax.tree.leaves(jax.device_get(state.params)),
        jax.tree.leaves(jax.device_get(restored.params)),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


@orbax_required
def test_orbax_restore_on_empty_dir_returns_none(tmp_path):
    from blendjax.train import CheckpointManager

    mgr = CheckpointManager(str(tmp_path))
    assert mgr.restore(_state()) is None
    mgr.close()


@orbax_required
def test_orbax_sharded_restore_preserves_shardings(tmp_path):
    from blendjax.train import CheckpointManager

    mesh = _mesh(8)
    state = _state(mesh=mesh)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, state)
    mgr.wait()
    template = _state(mesh=mesh)
    restored = mgr.restore(template)
    la = jax.tree.leaves(template.params)[0]
    lb = jax.tree.leaves(restored.params)[0]
    assert lb.sharding.device_set == la.sharding.device_set
    mgr.close()


@orbax_required
def test_orbax_async_save_overlaps_subsequent_step(tmp_path):
    from blendjax.train import CheckpointManager

    state = _state()
    step = make_supervised_step()
    batches = list(_batches(3, seed=7))
    state, _ = step(state, batches[0])
    ref = jax.tree.map(np.asarray, jax.device_get(state.params))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state)  # async: serialization overlaps the next steps
    # donating the state while orbax serializes would corrupt the
    # snapshot — train on with donate disabled, as documented
    step_nd = make_supervised_step(donate=False)
    for b in batches[1:]:
        state, _ = step_nd(state, b)
    mgr.wait()
    restored = mgr.restore(_state())
    for a, b in zip(jax.tree.leaves(ref),
                    jax.tree.leaves(jax.device_get(restored.params))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_cross_layout_resume_f32_identical(tmp_path):
    """Cross-layout resume (the layout-system satellite): train under
    data×fsdp, snapshot, restore onto a pure-data mesh (counted as a
    resharded restore), and the continuation is f32-identical to the
    leg that never stopped."""
    from blendjax.parallel import resolve_layout
    from blendjax.train.mesh_driver import make_mesh_supervised_step

    reg.reset()
    img = np.zeros((B, HW, HW, 4), np.uint8)
    model = CubeRegressor(features=(8,), dtype=np.float32)
    mesh_f = resolve_layout("data2xfsdp4").create_mesh()
    state = make_train_state(
        model, img, mesh=mesh_f, layout="data2xfsdp4"
    )
    step_f = make_mesh_supervised_step(state, mesh_f)
    bs_f = batch_sharding(mesh_f)
    batches = list(_batches(4, seed=3))
    for b in batches[:2]:
        state, _ = step_f(
            state, {k: jax.device_put(v, bs_f) for k, v in b.items()}
        )
    with SnapshotManager(str(tmp_path)) as mgr:
        mgr.save_async(2, state)
        mgr.wait()
        mesh_d = _mesh(8)
        template = make_train_state(model, img, mesh=mesh_d)
        res = mgr.restore(template, mesh=mesh_d)
    assert res.resharded
    assert reg.report()["counters"]["ckpt.resharded_restores"] >= 1
    # every restored leaf landed on the pure-data mesh
    leaf = jax.tree_util.tree_leaves(res.state.params)[0]
    assert len(leaf.sharding.device_set) == 8
    # continue both legs on identical data: losses equal to f32
    # reduction rounding (tests/test_mesh_driver.py's F32_EXACT_ATOL;
    # measured here 0.0)
    step_d = make_mesh_supervised_step(res.state, mesh_d)
    bs_d = batch_sharding(mesh_d)
    st_f, st_d = state, res.state
    for b in batches[2:]:
        st_f, mf = step_f(
            st_f, {k: jax.device_put(v, bs_f) for k, v in b.items()}
        )
        st_d, md = step_d(
            st_d, {k: jax.device_put(v, bs_d) for k, v in b.items()}
        )
        np.testing.assert_allclose(
            np.asarray(mf["loss"]), np.asarray(md["loss"]),
            rtol=0, atol=5e-6,
        )
