"""blendjax.rl: trajectory replay, actor pool, fused learner steps,
the env-bound/learner-bound doctor, and checkpoint/resume — all
hermetic (a fake vector env; no sockets, no producers)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blendjax.models import QNetwork  # noqa: E402
from blendjax.rl import (  # noqa: E402
    ActorPool,
    HostQPolicy,
    RLTrainDriver,
    TrajectoryReservoir,
    diagnose_rl,
    make_dqn_step,
    make_pg_step,
    make_rl_train_state,
    np_mlp_forward,
)
from blendjax.utils.metrics import metrics  # noqa: E402


class FakeVecEnv:
    """Deterministic 4-dim vector env with fixed-horizon episodes and
    the BatchedRemoteEnv contract (auto-reset + final_observation)."""

    def __init__(self, n=4, horizon=12, seed=0):
        self.n = n
        self.h = horizon
        self.rng = np.random.default_rng(seed)
        self.t = np.zeros(n, int)
        self.steps = 0

    def _obs(self):
        return self.rng.normal(size=(self.n, 4)).astype(np.float32)

    def reset(self, seed=None):
        self.t[:] = 0
        return self._obs(), [{} for _ in range(self.n)]

    def step(self, actions):
        self.steps += 1
        self.t += 1
        done = self.t >= self.h
        obs = self._obs()
        infos = [{} for _ in range(self.n)]
        for i in np.flatnonzero(done):
            # terminal obs deliberately distinctive so tests can assert
            # it reached next_obs instead of the fresh episode's start
            infos[i]["final_observation"] = np.full(4, 9.0, np.float32)
            self.t[i] = 0
        return obs, np.ones(self.n, np.float32), done, infos


def _insert_batch(res, n=8, seed=0, with_ret=False):
    rng = np.random.default_rng(seed)
    batch = {
        "obs": rng.normal(size=(n, 4)).astype(np.float32),
        "action": rng.integers(0, 3, size=n).astype(np.int32),
        "reward": np.ones(n, np.float32),
        "done": np.zeros(n, bool),
        "next_obs": rng.normal(size=(n, 4)).astype(np.float32),
    }
    if with_ret:
        batch["ret"] = rng.normal(size=n).astype(np.float32)
    return res.insert(batch)


# -- TrajectoryReservoir ------------------------------------------------------


def test_reservoir_insert_gather_round_trip_and_wraparound():
    res = TrajectoryReservoir(16)
    slots = _insert_batch(res, 8)
    assert list(slots) == list(range(8))
    out = res.sample(np.arange(8))
    assert set(out) == {"obs", "action", "reward", "done", "next_obs"}
    assert out["obs"].shape == (8, 4)
    # wraparound keeps size at capacity and reuses slots
    for seed in range(1, 4):
        _insert_batch(res, 8, seed=seed)
    assert res.size == 16 and res.inserts == 32


def test_reservoir_insert_buffers_stable_in_place():
    from blendjax.testing.donation import tree_pointers

    res = TrajectoryReservoir(8)
    _insert_batch(res, 8)
    before = tree_pointers(dict(res._buffers, _prio=res._priorities))
    _insert_batch(res, 8, seed=1)
    after = tree_pointers(dict(res._buffers, _prio=res._priorities))
    known = {
        k: v for k, v in before.items() if v is not None
        and after.get(k) is not None
    }
    assert known, "runtime exposed no pointers to compare"
    for k in known:
        assert before[k] == after[k], f"{k} reallocated on insert"


def test_reservoir_rejects_shape_and_structure_drift():
    res = TrajectoryReservoir(8)
    _insert_batch(res, 4)
    with pytest.raises(ValueError, match="structure"):
        res.insert({"obs": np.zeros((2, 4), np.float32)})
    with pytest.raises(ValueError, match="field"):
        _insert = {
            "obs": np.zeros((2, 5), np.float32),
            "action": np.zeros(2, np.int32),
            "reward": np.zeros(2, np.float32),
            "done": np.zeros(2, bool),
            "next_obs": np.zeros((2, 4), np.float32),
        }
        res.insert(_insert)


def test_reservoir_exact_fresh_replayed_accounting():
    res = TrajectoryReservoir(8, rng=3)
    _insert_batch(res, 8)
    idx = np.array([0, 0, 1, 2], np.int32)
    res.draw_token(idx)
    # slot 0 twice in one batch: one fresh + one replay
    assert (res.fresh, res.replayed) == (3, 1)
    res.draw_token(np.array([0, 1, 3], np.int32))
    assert (res.fresh, res.replayed) == (4, 3)
    assert res.fresh + res.replayed == 4 + 3


def test_reservoir_uniform_compose_and_insufficient_fill():
    res = TrajectoryReservoir(16, rng=0)
    assert res.compose(4) is None  # empty
    _insert_batch(res, 4)
    # with-replacement sampling: a batch may exceed the resident count
    # (the learner's min_fill gate decides how much warmup to demand)
    idx, w = res.compose(8)
    assert idx.shape == (8,) and np.all(w == 1.0)
    assert set(idx) <= {0, 1, 2, 3}


def test_reservoir_prioritized_compose_follows_priorities():
    res = TrajectoryReservoir(
        8, rng=0, prioritized=True, priority_refresh_every=1
    )
    _insert_batch(res, 8)
    # slam slot 5's priority sky-high on device, as the learner would
    res.commit_priorities(res._priorities.at[5].set(1e6))
    res._draws = res._draws_at_refresh + res.priority_refresh_every
    idx, w = res.compose(64)
    frac5 = np.mean(idx == 5)
    assert frac5 > 0.9, f"priority 1e6 slot drawn only {frac5:.0%}"
    # importance weights: the over-sampled slot gets the SMALLEST one
    if (idx != 5).any():
        assert w[idx == 5].max() <= w[idx != 5].min() + 1e-6
    else:
        assert np.allclose(w, 1.0)  # max-normalized


def test_reservoir_state_dict_round_trip_continues_sampling():
    res = TrajectoryReservoir(8, rng=7, prioritized=True)
    _insert_batch(res, 8)
    res.draw_token(*res.compose(4))
    snap = res.state_dict()
    # same-seed twin restores and continues the exact sequence
    twin = TrajectoryReservoir(8, rng=7, prioritized=True)
    twin.load_state_dict(snap)
    a = res.compose(4)
    b = twin.compose(4)
    assert np.array_equal(a[0], b[0]) and np.allclose(a[1], b[1])
    assert twin.size == res.size and twin.inserts == res.inserts
    assert (twin.fresh, twin.replayed) == (res.fresh, res.replayed)
    got = twin.sample(np.arange(8))
    want = res.sample(np.arange(8))
    for k in want:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(want[k])
        )


def test_reservoir_capacity_mismatch_refuses_restore():
    res = TrajectoryReservoir(8)
    _insert_batch(res, 4)
    snap = res.state_dict()
    with pytest.raises(ValueError, match="capacity"):
        TrajectoryReservoir(16).load_state_dict(snap)


# -- host policy / actor pool -------------------------------------------------


def test_np_mlp_forward_matches_flax_apply():
    model = QNetwork(hidden=(16, 8), n_actions=3)
    obs = np.random.default_rng(0).normal(size=(5, 4)).astype(np.float32)
    params = model.init(jax.random.key(0), obs)["params"]
    want = np.asarray(model.apply({"params": params}, obs))
    got = np_mlp_forward(jax.device_get(params), obs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_host_q_policy_random_until_snapshot_then_greedy():
    pol = HostQPolicy(3, eps_start=0.0, eps_end=0.0, seed=0)
    obs = np.zeros((4, 4), np.float32)
    a = pol(None, obs)
    assert a.shape == (4,) and a.dtype == np.int32
    model = QNetwork(hidden=(8,), n_actions=3)
    params = jax.device_get(
        model.init(jax.random.key(1), obs)["params"]
    )
    q = np_mlp_forward(params, obs)
    greedy = pol(params, obs)
    assert np.array_equal(greedy, np.argmax(q, axis=-1))


def test_actor_pool_feeds_reservoir_with_final_obs_bootstrap():
    res = TrajectoryReservoir(256)
    env = FakeVecEnv(n=4, horizon=3)
    pool = ActorPool(env, res, HostQPolicy(3, seed=0))
    with pool:
        import time

        deadline = time.monotonic() + 20
        while res.inserts < 48 and time.monotonic() < deadline:
            time.sleep(0.01)
    assert res.inserts >= 48
    # exact identity: every env row stepped == one inserted transition
    assert pool.env_steps == res.inserts
    assert pool.episodes >= 4
    # done rows bootstrapped from final_observation (the 9.0 stamp),
    # never from the fresh episode's first obs
    out = res.sample(np.arange(res.size))
    done = np.asarray(out["done"])
    nxt = np.asarray(out["next_obs"])
    assert done.any()
    assert np.allclose(nxt[done], 9.0)
    assert not np.allclose(nxt[~done], 9.0)


def test_actor_pool_state_dict_round_trip():
    res = TrajectoryReservoir(64)
    pool = ActorPool(
        FakeVecEnv(n=2, horizon=4), res, HostQPolicy(3, seed=2)
    )
    pool.env_steps = 40
    pool.episodes = 5
    pool.episode_returns = [(8, 4.0), (40, 4.0)]
    pool.policy.calls = 17
    snap = pool.state_dict()
    twin = ActorPool(
        FakeVecEnv(n=2, horizon=4), res, HostQPolicy(3, seed=2)
    )
    twin.load_state_dict(snap)
    assert twin.env_steps == 40 and twin.episodes == 5
    assert twin.episode_returns == [(8, 4.0), (40, 4.0)]
    assert twin.policy.calls == 17


def test_actor_pool_surfaces_thread_errors_via_check():
    class DeadEnv(FakeVecEnv):
        def step(self, actions):
            raise RuntimeError("env exploded")

    res = TrajectoryReservoir(16)
    pool = ActorPool(DeadEnv(n=2), res, HostQPolicy(3))
    with pool:
        import time

        deadline = time.monotonic() + 10
        while pool._error is None and time.monotonic() < deadline:
            time.sleep(0.01)
    with pytest.raises(RuntimeError, match="actor loop died"):
        pool.check()
    # a restart after a transient death comes up healthy: start()
    # clears the stale error instead of re-raising it forever
    healthy = ActorPool(FakeVecEnv(n=2), res, HostQPolicy(3))
    healthy._error = RuntimeError("stale")
    with healthy:
        healthy.check()


# -- fused learner steps ------------------------------------------------------


def _train_setup(prioritized=False, pg=False, capacity=64):
    res = TrajectoryReservoir(capacity, rng=0, prioritized=prioritized)
    model = QNetwork(hidden=(16,), n_actions=3)
    state = make_rl_train_state(
        model, np.zeros((1, 4), np.float32), target=not pg
    )
    if pg:
        step = make_pg_step(res, model.apply)
    else:
        step = make_dqn_step(res, model.apply)
    return res, model, state, step


def test_dqn_step_one_dispatch_updates_state_and_priorities():
    res, model, state, step = _train_setup(prioritized=True)
    _insert_batch(res, 32)
    prio_before = np.array(res._priorities)
    p0 = jax.device_get(state.params)
    token = res.draw_token(*res.compose(16))
    state, m = step(state, token)
    assert np.isfinite(float(m["loss"]))
    p1 = jax.device_get(state.params)
    changed = any(
        not np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1))
    )
    assert changed, "params did not update"
    # priorities rewritten in-jit at the drawn slots
    prio_after = np.array(res._priorities)
    drawn = np.unique(token["_rl_idx"])
    assert not np.allclose(prio_before[drawn], prio_after[drawn])
    untouched = np.setdiff1d(np.arange(res.capacity), drawn)
    np.testing.assert_array_equal(
        prio_before[untouched], prio_after[untouched]
    )


def test_dqn_step_updates_params_ring_and_priorities_in_place():
    """The learner step donates the state and the priority vector and
    only reads the ring: across steps and inserts none of the three is
    copied (a copy trains the same and doubles the memory)."""
    from blendjax.testing.donation import DonationAudit

    res, model, state, step = _train_setup(prioritized=True)
    _insert_batch(res, 32)
    audit = DonationAudit()

    def mark():
        audit.snapshot("params", state.params)
        audit.snapshot("ring", res._buffers)
        audit.snapshot("priorities", res._priorities)

    state, _ = step(state, res.draw_token(*res.compose(16)))  # compile
    state, _ = step(state, res.draw_token(*res.compose(16)))  # settle
    for i in range(4):
        mark()
        _insert_batch(res, 8, seed=i + 1)
        state, m = step(state, res.draw_token(*res.compose(16)))
    jax.block_until_ready(m["loss"])
    mark()
    for label in ("params", "ring", "priorities"):
        audit.assert_stable(label)
        assert audit.report()[label]["snapshots"] == 5


def test_dqn_target_polyak_moves_inside_the_same_dispatch():
    res, model, state, step = _train_setup()
    _insert_batch(res, 32)
    t0 = jax.device_get(state.target_params)
    state, _ = step(state, res.draw_token(*res.compose(16)))
    t1 = jax.device_get(state.target_params)
    moved = any(
        not np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(t0), jax.tree.leaves(t1))
    )
    assert moved, "target network froze (tau ignored)"


def test_pg_step_trains_on_returns():
    res, model, state, step = _train_setup(pg=True)
    rng = np.random.default_rng(0)
    res.insert({
        "obs": rng.normal(size=(32, 4)).astype(np.float32),
        "action": rng.integers(0, 3, size=32).astype(np.int32),
        "reward": np.ones(32, np.float32),
        "done": np.zeros(32, bool),
        "next_obs": rng.normal(size=(32, 4)).astype(np.float32),
        "ret": rng.normal(size=32).astype(np.float32),
    })
    state, m = step(state, res.draw_token(*res.compose(16)))
    assert np.isfinite(float(m["loss"]))


@pytest.mark.parametrize("n_dev", [None, 8], ids=["one-device", "mesh8"])
def test_learner_driver_end_to_end_exact_accounting(n_dev):
    """Actors -> reservoir -> one-dispatch learner, on one device and
    with ring, priorities and state laid out over an 8-device mesh
    (``mesh_rl_step_kwargs``): the same counts either way."""
    from blendjax.parallel import create_mesh
    from blendjax.rl import mesh_rl_step_kwargs

    metrics.reset()
    mesh = n_dev and create_mesh({"data": n_dev})
    res = TrajectoryReservoir(128, rng=0, prioritized=True, mesh=mesh)
    env = FakeVecEnv(n=4, horizon=8)
    pool = ActorPool(env, res, HostQPolicy(3, eps_steps=64, seed=1))
    model = QNetwork(hidden=(16,), n_actions=3)
    state = make_rl_train_state(
        model, np.zeros((1, 4), np.float32), mesh=mesh
    )
    step = make_dqn_step(
        res, model.apply,
        **(mesh_rl_step_kwargs(state, mesh) if mesh else {}),
    )
    driver = RLTrainDriver(
        step, state, res, actors=pool, mesh=mesh, batch_size=16,
        min_fill=32, sync_every=4, inflight=2,
    )
    with pool:
        loss = driver.run_steps(12)
    assert np.isfinite(loss)
    assert driver.steps == 12 and driver.dispatches == 12
    spans = metrics.report()["spans"]
    assert spans["train.dispatch"]["count"] == 12  # and no gather beside it
    assert "rl.sample" not in spans
    for leaf in (res._priorities, *jax.tree.leaves(res._buffers)):
        assert len(leaf.sharding.device_set) == (n_dev or 1)
    # the seq-style identity: every drawn row accounted exactly once
    assert res.fresh + res.replayed == 12 * 16
    # actors got >= 12/4 policy snapshots
    assert pool.policy_version >= 3
    # driver stats carry the rl sub-views
    s = driver.stats
    assert s["reservoir"]["draws"] == 12
    assert s["actor"]["env_steps"] == res.inserts


def test_learner_driver_times_out_without_actors():
    res = TrajectoryReservoir(64)
    model = QNetwork(hidden=(8,), n_actions=3)
    state = make_rl_train_state(model, np.zeros((1, 4), np.float32))
    step = make_dqn_step(res, model.apply)
    driver = RLTrainDriver(
        step, state, res, batch_size=8, sample_timeout_s=0.2,
    )
    with pytest.raises(TimeoutError, match="reservoir never reached"):
        driver.train_step()


def test_learner_driver_session_round_trip(tmp_path):
    """An RL run checkpoints through the PR 11 session store and a
    fresh process-equivalent stack resumes mid-curve."""
    from blendjax.checkpoint import SnapshotManager

    res = TrajectoryReservoir(64, rng=0, prioritized=True)
    env = FakeVecEnv(n=2, horizon=6)
    pool = ActorPool(env, res, HostQPolicy(3, seed=3))
    model = QNetwork(hidden=(8,), n_actions=3)
    state = make_rl_train_state(model, np.zeros((1, 4), np.float32))
    step = make_dqn_step(res, model.apply)
    with SnapshotManager(str(tmp_path)) as mgr:
        driver = RLTrainDriver(
            step, state, res, actors=pool, batch_size=8, min_fill=16,
            checkpoint=mgr, inflight=1,
        )
        with pool:
            driver.run_steps(5)
        # actors stopped: the snapshot captures a quiesced stack, so
        # the restored twin compares exactly against the live one
        driver.checkpoint_now(wait=True)
        steps_at_save = driver.steps

        # fresh stack (same construction), restored from the snapshot
        res2 = TrajectoryReservoir(64, rng=0, prioritized=True)
        pool2 = ActorPool(
            FakeVecEnv(n=2, horizon=6), res2, HostQPolicy(3, seed=3)
        )
        model2 = QNetwork(hidden=(8,), n_actions=3)
        state2 = make_rl_train_state(
            model2, np.zeros((1, 4), np.float32)
        )
        restored = mgr.restore(state2)
        step2 = make_dqn_step(res2, model2.apply)
        driver2 = RLTrainDriver(
            step2, restored.state, res2, actors=pool2, batch_size=8,
            min_fill=16, inflight=1,
        )
        names = driver2.restore_session(restored.session)
        assert set(names) == {"replay", "actor", "driver"}
        assert driver2.steps == steps_at_save
        assert res2.inserts == res.inserts
        assert pool2.env_steps == pool.env_steps
        # the restored ring serves draws immediately (no actors needed:
        # the transitions came back with the snapshot)
        with pool2:
            loss = driver2.run_steps(2)
        assert np.isfinite(loss)
        assert driver2.steps == steps_at_save + 2


# -- the RL doctor ------------------------------------------------------------


def _report(counters=None, spans=None):
    return {"counters": counters or {}, "spans": spans or {},
            "gauges": {}}


def test_diagnose_rl_idle_without_evidence():
    v = diagnose_rl(_report())
    assert v.kind == "rl-idle"


def test_diagnose_rl_env_bound_on_sustained_sample_waits():
    v = diagnose_rl(_report(
        {"rl.transitions": 100, "rl.fresh": 90, "rl.replayed": 110,
         "rl.draws": 20, "rl.sample_waits": 3},
        {"rl.sample_wait": {"total_ms": 1200.0}},
    ))
    assert v.kind == "env-bound"
    assert "scale UP" in v.advice


def test_diagnose_rl_single_warmup_wait_is_not_sticky():
    """Every run starts with one wait at min_fill; as healthy draws
    accumulate the signal must dilute below the wait-fraction bar —
    a bare waits>0 test would ratchet the fleet to max forever."""
    v = diagnose_rl(_report(
        {"rl.transitions": 100, "rl.fresh": 100, "rl.replayed": 400,
         "rl.draws": 500, "rl.sample_waits": 1}
    ))
    assert v.kind == "rl-balanced"


def test_diagnose_rl_learner_bound_on_insert_surplus():
    v = diagnose_rl(_report(
        {"rl.transitions": 1000, "rl.fresh": 100, "rl.replayed": 100}
    ))
    assert v.kind == "learner-bound"
    assert "scale DOWN" in v.advice


def test_diagnose_rl_balanced_when_replay_absorbs_the_gap():
    v = diagnose_rl(_report(
        {"rl.transitions": 100, "rl.fresh": 100, "rl.replayed": 500}
    ))
    assert v.kind == "rl-balanced"


def test_fleet_controller_scales_on_rl_verdicts():
    """FleetPolicy.rl() + the RL verdict vocabulary drive the existing
    controller machinery unchanged (hysteresis included)."""
    from blendjax.fleet import FleetController, FleetPolicy

    class StubLauncher:
        def __init__(self):
            self.n = 1
            self.sockets = {0: {"DATA": "tcp://127.0.0.1:1"}}

        def active_indices(self):
            return list(range(self.n))

        def active_count(self):
            return self.n

        def poll_processes(self):
            return {i: None for i in self.active_indices()}

        def add_instance(self, extra_args=None):
            i = self.n
            self.n += 1
            s = {"DATA": f"tcp://127.0.0.1:{i + 1}"}
            self.sockets[i] = s
            return i, s

        def retire_instance(self, i, drain=True):
            self.n -= 1
            return self.sockets[i]

    class StubConnector:
        def __init__(self):
            self.connected = []

        def connect(self, addr):
            self.connected.append(addr)

        def disconnect(self, addr):
            self.connected.remove(addr)

    class StubLineage:
        def register(self, btid):
            pass

        def retire(self, btid):
            pass

    policy = FleetPolicy.rl(up_after=2, down_after=2, cooldown_s=0.0,
                            max_instances=3)
    assert policy.scale_up_verdicts == ("env-bound",)
    ctrl = FleetController(
        StubLauncher(), connector=StubConnector(), policy=policy,
        lineage=StubLineage(),
    )
    t = 0.0
    assert ctrl.tick("env-bound", now=t)["action"] == "hold"
    d = ctrl.tick("env-bound", now=t + 1)
    assert d["action"] == "scale_up" and d["instances"] == 2
    # learner-bound streak scales back down
    ctrl.tick("learner-bound", now=t + 2)
    d = ctrl.tick("learner-bound", now=t + 3)
    assert d["action"] == "scale_down"
    # rl-balanced resets streaks
    ctrl.tick("rl-balanced", now=t + 4)
    assert ctrl._up_streak == 0 and ctrl._down_streak == 0


# -- BJX117 regression: every reservoir entry point holds `lock` -------------


class CountingLock:
    """Context-manager probe standing in for the reservoir RLock."""

    def __init__(self):
        self.inner = __import__("threading").RLock()
        self.entries = 0

    def __enter__(self):
        self.entries += 1
        return self.inner.__enter__()

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)


def _filled_reservoir(**kw):
    res = TrajectoryReservoir(8, **kw)
    res.insert({
        "obs": np.zeros((4, 3), np.float32),
        "reward": np.ones(4, np.float32),
    })
    return res


def test_reservoir_stats_and_fields_take_the_lock():
    """PR 11's snapshot-vs-draw race class, pinned: the observability
    reads share the insert/draw critical section (BJX117 flags any
    regression statically; this is the runtime half)."""
    res = _filled_reservoir()
    probe = CountingLock()
    res.lock = probe
    assert res.stats["inserts"] == 4
    assert probe.entries == 1
    assert len(res.fields) == 2
    assert probe.entries == 2


def test_reservoir_empty_checks_run_under_the_lock():
    """draw_token/sample raise the empty-reservoir error from INSIDE
    the critical section (the pre-lock check read `_buffers` unlocked)."""
    res = TrajectoryReservoir(4)
    probe = CountingLock()
    res.lock = probe
    with pytest.raises(RuntimeError, match="insert"):
        res.draw_token(np.zeros(2, np.int32))
    with pytest.raises(RuntimeError, match="insert"):
        res.sample(np.zeros(2, np.int32))
    assert probe.entries == 2


def test_actor_stats_and_restore_share_the_accounting_cut():
    res = _filled_reservoir()
    probe = CountingLock()
    res.lock = probe
    pool = ActorPool(FakeVecEnv(), res, HostQPolicy(2))
    before = probe.entries
    assert pool.stats["env_steps"] == 0
    assert probe.entries == before + 1
    pool.load_state_dict({"env_steps": 7, "episodes": 1,
                          "episode_returns": [[7, 1.5]]})
    assert probe.entries == before + 2
    assert pool.stats["env_steps"] == 7
