"""Guards for bench.py's measurement helpers (a silent mis-measurement
is worse than a crash)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def test_model_flops_is_the_ledger_probe():
    """bench.py re-exports the device ledger's probe (the one home for
    the cost-model path) — a second copy drifting in bench.py is how
    the MFU denominator silently forks."""
    import bench

    from blendjax.obs import devledger

    assert bench.measure_model_flops is devledger.measure_model_flops


def test_model_flops_matches_analytic_count():
    """cost_analysis-derived FLOPs/img must agree with the analytic
    conv count — catches the lax.scan-body-counted-once class of bug
    (r4 shipped a 16x undercount briefly) and any future model/shape
    drift that silently changes the MFU denominator."""
    import bench

    fl = bench.measure_model_flops()
    got = fl["flops_per_image"]

    # Analytic fwd FLOPs for CubeRegressor at 480x640: stride-2 3x3
    # convs (32, 64, 128, 256) + the dense head; backward ~2x forward.
    h, w, cin = 480, 640, 4
    fwd = 0
    for f in (32, 64, 128, 256):
        h, w = h // 2, w // 2
        fwd += 2 * 9 * cin * f * h * w
        cin = f
    fwd += 2 * 256 * 256 + 2 * 256 * 16  # dense head
    analytic = 3 * fwd  # fwd + ~2x bwd
    assert 0.7 * analytic < got < 1.3 * analytic, (got, analytic)


def test_tile_capacity_default_derives_from_dims():
    """Measured geometries keep their measured fits; any other geometry
    gets an area-scaled estimate that covers the known changed-pixel
    budget (ADVICE r4: a 32x32 override silently got the 16x16 fit)."""
    import bench

    assert bench.tile_capacity_default(16, 16) == "288"
    assert bench.tile_capacity_default(16, 32) == "160"
    cap = int(bench.tile_capacity_default(32, 32))
    grid = 15 * 20  # 480/32 x 640/32
    assert 32 <= cap <= grid and cap % 32 == 0
    assert cap * 32 * 32 >= 282 * 256  # covers the measured budget
    # tiny grids (huge tiles) clamp to the grid, not up to 32
    assert int(bench.tile_capacity_default(240, 320)) == 4


def _stub_rows(monkeypatch, values, raising=None):
    """Replace every measurement bench._build_record makes with a stub:
    the headline passes return ``values`` in turn, each add-on row a
    small dict (``raising`` names one that raises instead)."""
    import bench

    passes = iter(values)
    monkeypatch.setattr(
        bench, "measure",
        lambda *a, **k: {"value": next(passes), "seconds": 1.0, "chunk": 16},
    )
    def row(name):
        def fn(*a, **k):
            if name == raising:
                raise RuntimeError(f"{name} broke")
            return {"img_s": 100.0}

        return fn

    for name in vars(bench):
        if name.startswith("measure_") or name == "_raw_row":
            monkeypatch.setattr(bench, name, row(name))


@pytest.mark.usefixtures("compile_cache_config_guard")
def test_build_record_takes_plain_passes_and_names_platforms(monkeypatch):
    """BLENDJAX_BENCH_PASSES plain passes, the best one reported; no
    probe, window or shrunken workload anywhere; every row names the
    platform it ran on."""
    import bench

    monkeypatch.setenv("BLENDJAX_BENCH_PASSES", "3")
    _stub_rows(monkeypatch, [10.0, 50.0, 20.0])
    rec = bench._build_record()
    detail = rec["detail"]
    assert rec["value"] == 50.0
    assert [p["value"] for p in detail["passes"]] == [10.0, 50.0, 20.0]
    assert detail["platform"] == "cpu" and detail["device_count"] >= 1
    assert detail["utilization"] == 0.5
    assert detail["utilization_vs_ceiling"] == 0.5
    rows = {k: v for k, v in detail.items() if isinstance(v, dict)}
    assert len(rows) >= 16
    assert all(v["platform"] == "cpu" for v in rows.values()), rows
    # and nothing else is stamped on the record
    assert {k for k in detail if k not in rows} == {
        "seconds", "chunk", "platform", "device_kind", "device_count",
        "passes", "utilization", "utilization_vs_ceiling",
    }


@pytest.mark.usefixtures("compile_cache_config_guard")
@pytest.mark.parametrize(
    "raising", ["measure_live_fleet", "measure_step_alone", "measure_rl_hz"]
)
def test_a_raising_row_fails_the_run(monkeypatch, capsys, raising):
    """No row is turned into an ``{"error": ...}`` field of a record
    that still prints and exits 0: the exception leaves main()."""
    import bench

    monkeypatch.setenv("BLENDJAX_BENCH_PASSES", "1")
    _stub_rows(monkeypatch, [10.0], raising=raising)
    with pytest.raises(RuntimeError, match="broke"):
        bench.main()
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize(
    "returncode, stdout",
    [(3, '{"img_s": 1.0}'), (0, "no json line here")],
    ids=["nonzero-exit", "no-record"],
)
def test_cpu_mesh_child_raises_on_a_failed_child(
    monkeypatch, returncode, stdout
):
    """The forced-CPU mesh legs run in children; one that exits non-zero
    or prints no record raises in the parent instead of becoming an
    error row — and one that succeeds is stamped ``"platform": "cpu"``."""
    import subprocess

    import bench

    def fake_run(argv, **kw):
        return subprocess.CompletedProcess(argv, returncode, stdout, "boom")

    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="--multichip-live.*boom"):
        bench.measure_multichip_live()
    monkeypatch.setattr(
        subprocess, "run",
        lambda argv, **kw: subprocess.CompletedProcess(
            argv, 0, 'noise\n{"img_s": 1.0}', ""
        ),
    )
    assert bench.measure_multichip_live() == {"img_s": 1.0, "platform": "cpu"}


def test_pipelined_ceiling_caps_and_flags(monkeypatch):
    """A ceiling run that exceeds its time cap must return what it
    measured, flagged 'capped' (a silently depressed ceiling would
    publish utilization_vs_ceiling > 1 as if live beat the runtime).

    Bench-shape constants are shrunk for the CPU mesh (the cap logic is
    shape-independent; full 640x480 CPU convs would cost ~6 min)."""
    import bench

    monkeypatch.setattr(bench, "SHAPE", (64, 64))
    monkeypatch.setattr(bench, "BATCH", 8)
    out = bench.measure_pipelined_ceiling(2, items=32, time_cap=0.0)
    assert out["images"] > 0 and out["img_s"] > 0
    assert out.get("capped") is True


def test_live_overlap_row_shape(monkeypatch):
    """The async-overlap A/B row runs both legs for real through the
    fused driver path and reports the record's contract: zero
    standalone decode dispatches, exactly one jit call per driver step
    (the bench-smoke CI assertion), driver ring stats, and the
    throughput ratio. Bench shapes shrunk for the CPU mesh like the
    rows above."""
    import bench

    monkeypatch.setattr(bench, "SHAPE", (64, 64))
    monkeypatch.setattr(bench, "_TILE_ARGS", ["16"])
    monkeypatch.setattr(bench, "TILE_CAPACITY", "16")
    monkeypatch.setenv("BLENDJAX_BENCH_INSTANCES", "2")
    row = bench.measure_live_overlap(
        chunk=2, items=16, time_cap=10.0, inflight=3
    )
    assert row["inflight1"]["img_s"] > 0
    assert row["inflight3"]["img_s"] > 0
    assert row["decode_dispatch_eliminated"] is True
    assert row["dispatch_per_step"] == 1.0
    for leg in ("inflight1", "inflight3"):
        assert row[leg]["decode_dispatch_count"] == 0
        assert row[leg]["train_dispatch_count"] == row[leg]["dispatches"]
        assert row[leg]["steps_in_flight_hwm"] <= 3
    assert row["value"] == pytest.approx(
        row["inflight3"]["img_s"] / row["inflight1"]["img_s"], rel=1e-3
    )


def test_live_echo_row_shape(monkeypatch):
    """The data-echoing A/B row runs the off and echo legs for real
    through pipeline + reservoir + TrainDriver and reports the record's
    contracts: exact echo accounting (fresh + echoed == steps * batch),
    exactly one DEVICE dispatch per driver step under FULL accounting
    (train + standalone reservoir gathers + decodes — the echo leg runs
    the fused draw, so standalone gathers are zero), the donation-reuse
    audit, unique fraction, and the step-rate ratio. Bench shapes
    shrunk for the CPU mesh like the rows above."""
    import bench

    monkeypatch.setattr(bench, "SHAPE", (64, 64))
    monkeypatch.setattr(bench, "_TILE_ARGS", ["16"])
    monkeypatch.setattr(bench, "TILE_CAPACITY", "16")
    row = bench.measure_live_echo(
        items=16, time_cap=10.0, factors=(4,), capacity=64
    )
    assert row["off"]["step_img_s"] > 0
    assert row["echo4"]["step_img_s"] > 0
    assert row["accounting_exact"] is True
    assert row["dispatch_per_step"] == 1.0
    leg = row["echo4"]
    assert leg["max_echo_factor"] == 4
    assert leg["fused_draw"] is True
    # the full dispatch accounting's teeth: zero standalone reservoir
    # gathers at the step cadence (pre-fusion this was one per step)
    assert leg["echo_sample_dispatches"] == 0
    # the runtime donation audit held on every leg: ring + state
    # buffers updated in place, never copied
    assert row["donation_reuse"] is True
    assert leg["donation_audit"]["reservoir"]["stable"] is True
    assert leg["donation_audit"]["state"]["stable"] is True
    assert 0.0 < leg["unique_fraction"] <= 1.0
    assert leg["echo_counters"]["echo.fresh"] + leg["echo_counters"][
        "echo.echoed"
    ] == leg["steps"] * bench.BATCH
    assert row["off"]["unique_fraction"] == 1.0
    assert row["value"] == pytest.approx(
        row["echo4"]["step_img_s"] / row["off"]["step_img_s"], abs=5e-4
    )


def test_precision_ab_row_shape():
    """The precision A/B row reports BOTH policies with step-alone
    img/s and an mfu_step_alone key on the CNN and longseq legs (None
    off-v5e — the key's presence is the CI structural assertion), plus
    the throughput ratios."""
    import bench

    row = bench.measure_precision_ab()
    assert set(row["legs"]) == {"bf16-compute", "bf16-grads"}
    for leg in row["legs"].values():
        for sub in ("cnn", "longseq"):
            assert leg[sub]["img_s"] > 0
            assert "mfu_step_alone" in leg[sub]
        assert leg["longseq"]["tokens"] > 0
    assert row["value"] > 0
    assert row["longseq_ratio"] > 0
    assert row["full_geometry"] is False  # CPU suite runs shrunk shapes


def test_ingest_workers_ab_row_shape(monkeypatch):
    """The sharded-ingest A/B row runs both legs for real and reports
    the contract the record promises: per-shard ingest.recv spans on
    the workers-2 leg, the wire byte pair on both, and the throughput
    ratio. Bench-shape constants shrunk for the CPU mesh like the
    ceiling test above."""
    import bench

    monkeypatch.setattr(bench, "SHAPE", (64, 64))
    monkeypatch.setattr(bench, "_TILE_ARGS", ["16"])
    monkeypatch.setattr(bench, "TILE_CAPACITY", "16")
    monkeypatch.setenv("BLENDJAX_BENCH_INSTANCES", "2")
    row = bench.measure_ingest_workers_ab(chunk=2, items=16, time_cap=10.0)
    assert row["workers1"]["img_s"] > 0 and row["workers2"]["img_s"] > 0
    assert row["value"] == pytest.approx(
        row["workers2"]["img_s"] / row["workers1"]["img_s"], rel=1e-3
    )
    assert "ingest.recv" in row["workers1"]["recv_spans"]
    shard_spans = set(row["workers2"]["recv_spans"])
    assert {"ingest.recv.shard0", "ingest.recv.shard1"} <= shard_spans
    for leg in ("workers1", "workers2"):
        wire = row[leg]["wire"]
        assert wire["wire.raw_bytes"] >= wire["wire.compressed_bytes"] > 0


def test_multichip_live_legs_shape(monkeypatch):
    """The multichip_live row runs the REAL live mesh path (synthetic
    producers -> sharded ingest -> mesh feeder -> MeshTrainDriver) per
    mesh size and reports the record's contracts: one dispatch per
    step at every size, zero decode dispatches, zero wire gaps, and
    the weak-scaling speedup/efficiency pair. Shrunk to two mesh sizes
    and short windows for the CPU suite; the structure is identical to
    the full 1/2/4/8 row."""
    import bench

    monkeypatch.setattr(bench, "MULTICHIP_PASSES", 1)
    row = bench._multichip_live_legs(mesh_sizes=(1, 4), time_cap=1.5)
    assert set(row["legs"]) == {"1", "4"}
    for n, leg in row["legs"].items():
        assert leg["img_s"] > 0
        assert leg["global_batch"] == row["b_dev"] * int(n)
        assert leg["dispatch_per_step"] == 1.0
        assert leg["decode_dispatch_count"] == 0
    assert row["seq_gaps"] == 0
    assert row["contracts_held_every_pass"] is True
    assert row["dispatch_per_step"] == 1.0
    assert row["decode_dispatch_eliminated"] is True
    assert row["speedup"] == pytest.approx(
        row["legs"]["4"]["img_s"] / row["legs"]["1"]["img_s"], rel=1e-3
    )
    assert row["scaling_efficiency"] == pytest.approx(
        row["speedup"] / 4, rel=1e-2
    )


def test_live_resume_row_shape(tmp_path, monkeypatch):
    """The kill-9/resume row runs its three child processes for real
    (uninterrupted reference, paced-then-SIGKILLed, resumed) and
    reports the record's contracts: identical f32 trajectories, zero
    wire gaps with the restart detected through the restored lineage,
    one dispatch per step with checkpointing enabled, and >= 1
    committed async save. Shrunk step count for the CPU suite."""
    import bench

    monkeypatch.setattr(bench, "RESUME_DIR", str(tmp_path / "snaps"))
    row = bench.measure_live_resume(steps=8)
    assert row["equality"]["identical"] is True
    assert row["equality"]["max_abs_diff"] == 0.0
    assert row["killed_mid_run"] is True
    assert row["committed_before_kill"] is True
    assert row["resumed_at"] >= 1
    assert row["seq_gaps"] == 0
    assert row["restart_detected"] is True
    assert row["dispatch_per_step"] == 1.0
    assert row["ckpt"]["saves"] >= 1
    assert row["value"] == 1.0


def test_wire_equality_contract():
    """live_wire_ab's equality leg, standalone: the SAME recorded wire
    bytes decoded as deferred "ndr" (run-length expansion inside the
    fused train dispatch) vs host-inflated "nd" fields train to
    IDENTICAL f32 loss — the device decompression changes where the
    bytes expand, never what the step computes."""
    import bench

    row = bench.measure_wire_equality(steps=6)
    assert row["identical"] is True
    assert row["max_abs_diff"] == 0.0
    assert row["ndr_loss"] == row["nd_loss"]


@pytest.mark.slow
def test_live_wire_ab_row_shape(monkeypatch):
    """The full wire-decode A/B row against real rate-capped synthetic
    producers: both legs report wire bytes + host decode-cost p95 +
    settled rates, the ndr leg holds the one-dispatch contract with
    ZERO standalone decode dispatches, no wire gaps, and the
    live-to-step-alone ratio is computed against the SAME fused step."""
    import bench

    row = bench.measure_live_wire_ab(time_cap=6.0)
    for name in ("ndz", "ndr"):
        leg = row[name]
        assert leg["steps"] > 0, (name, leg)
        assert leg["wire_bytes"] > 0, (name, leg)
        assert "decode_ms_p95" in leg and "settled_img_s" in leg
    assert row["ndr"]["dispatch_per_step"] == 1.0, row["ndr"]
    assert row["ndr"]["decode_dispatch_count"] == 0, row["ndr"]
    assert row["ndr"]["decode_ms_p95"] == 0.0, row["ndr"]
    assert row["ndr"]["rle_counters"].get("rle.batches", 0) > 0
    assert row["seq_gaps"] == 0, row
    assert row["equality"]["identical"] is True, row["equality"]
    assert row["step_alone"]["img_s"] > 0
    assert row["value"] == row["live_to_alone"] > 0
