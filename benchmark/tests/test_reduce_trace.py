"""The reduction from a trace to busy, idle, collective and gap times,
against a trace whose intervals are known because they were written by
hand (``xplane_writer``), and against its recorded copy
``known_intervals.xplane.pb``."""

import os

import pytest

import reduce_trace
import xplane_writer

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1_000.0  # the trace below is laid out in microseconds

# Window 0..1000 us. Device 0: a fusion 100..200; a while 300..700 whose
# body holds a fusion 300..400, an all-reduce-done 500..600 (nothing
# beside it: exposed) and an all-gather 620..660 nested under a fusion
# 600..700 of the other kind (not a leaf, so not counted). Busy =
# 100 + 400 = 500 us. Device 1: one fusion 0..750 and an all-reduce
# 750..800 -> busy 800 us, exposed collective 50 us.
PLANES = {
    "/device:TPU:0": {
        "XLA Ops": [
            ("fusion.1", 100 * US, 200 * US),
            ("while.1", 300 * US, 700 * US),
            ("fusion.2", 300 * US, 400 * US),
            ("all-reduce-done.1", 500 * US, 600 * US),
            ("fusion.3", 600 * US, 700 * US),
        ],
        "XLA Modules": [
            ("jit__fused(1)", 0.0, 10 * US),       # cut by the trace's start
            ("jit__fused(1)", 100 * US, 200 * US),
            ("jit__fused(1)", 300 * US, 700 * US),
            ("jit__fused(1)", 900 * US, 1000 * US),  # cut by its stop
        ],
        "Steps": [("0", 0.0, 1000 * US)],
    },
    "/device:TPU:1": {
        "XLA Ops": [
            ("fusion.1", 0.0, 750 * US),
            ("all-reduce.7", 750 * US, 800 * US),
        ],
    },
    "/host:CPU": {
        "main": [
            ("bench.trace_window", 0.0, 1000 * US),
            ("bench.next", 180 * US, 320 * US),
            ("bench.drain", 690 * US, 1000 * US),
            ("something else", 0.0, 1000 * US),
        ],
    },
}


@pytest.fixture(params=["written", "recorded"])
def summary(request, tmp_path):
    if request.param == "written":
        path = str(tmp_path / "t.xplane.pb")
        xplane_writer.write(path, PLANES)
    else:
        path = os.path.join(HERE, "known_intervals.xplane.pb")
    return reduce_trace.summarize(reduce_trace.read_planes(path))


def test_recorded_copy_is_the_written_trace():
    with open(os.path.join(HERE, "known_intervals.xplane.pb"), "rb") as f:
        assert f.read() == xplane_writer.xspace(PLANES)


def test_busy_idle_and_window(summary):
    assert summary["window_s"] == pytest.approx(1000e-6)
    d0, d1 = summary["devices"]
    assert d0["busy_s"] == pytest.approx(500e-6)
    assert d0["idle_share"] == pytest.approx(0.5)
    assert d1["busy_s"] == pytest.approx(800e-6)
    assert d1["idle_share"] == pytest.approx(0.2)
    assert summary["busy_s"] == pytest.approx(650e-6)  # mean over chips
    assert d0["longest_gap_s"] == pytest.approx(300e-6)


def test_collectives_count_only_where_nothing_else_runs(summary):
    d0, d1 = summary["devices"]
    assert d0["collective_s"] == pytest.approx(100e-6)
    assert d0["collective_exposed_s"] == pytest.approx(100e-6)
    assert d1["collective_exposed_s"] == pytest.approx(50e-6)


def test_self_time_does_not_count_a_loop_body_twice(summary):
    ops = dict(summary["device_ops"])
    # while.1 spans 400 us, 300 of them covered by its body
    assert ops["while.1"] * 2 == pytest.approx(100e-6)
    # fusion.1: 100 us on device 0 and 750 us on device 1, mean of two
    assert ops["fusion.1"] * 2 == pytest.approx(850e-6)
    assert len(summary["device_ops"]) <= 10


def test_idle_gaps_go_to_what_the_host_was_doing(summary):
    gaps = dict(summary["idle_gaps"])
    # device 0: 0..100 nobody, 200..300 bench.next, 700..1000 bench.drain
    # device 1: 800..1000 bench.drain; mean over the two devices
    assert gaps["bench.drain"] * 2 == pytest.approx(500e-6)
    assert gaps["bench.next"] * 2 == pytest.approx(100e-6)
    assert gaps["unannotated"] * 2 == pytest.approx(100e-6)
    assert "something else" not in gaps


def test_only_whole_program_executions_count(summary):
    m = summary["modules"]["jit__fused(1)"]
    # the first and last events of the line may be cut by the trace
    assert m["executions"] == 2
    assert m["seconds_per_execution"] == pytest.approx(250e-6)
    # busy inside them: 100 us of 100, and 400 us of 400
    assert m["busy_s_per_execution"] == pytest.approx(250e-6)


def test_interval_arithmetic():
    assert reduce_trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert reduce_trace.subtract([(0, 10)], [(2, 3), (5, 11)]) == [
        (0, 2), (3, 5)
    ]
    assert reduce_trace.total([(0, 3), (5, 7)]) == 5


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        reduce_trace.summarize({"/host:CPU": {"main": [("x", 0.0, 1.0)]}})


def test_a_trace_recorded_on_the_v5e(tmp_path):
    """Three synchronous executions of the cube CNN's fused step on one
    TPU v5e, traced in PR 22 (the host clock read 0.1311 s a step). The
    reduction has to find the device plane and its lines under the names
    the chip's profiler writes, count only the execution the trace holds
    whole, and see the device busy all through it."""
    import gzip
    import shutil

    path = str(tmp_path / "v5e.xplane.pb")
    with gzip.open(
        os.path.join(HERE, "v5e_cube_step_x3.xplane.pb.gz"), "rb"
    ) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    planes = reduce_trace.read_planes(path)
    assert "/device:TPU:0" in planes and "/host:CPU" in planes
    assert len(planes["/device:TPU:0"]["XLA Modules"]) == 3
    got = reduce_trace.summarize(planes)
    (device,) = got["devices"]
    assert device["ops"] > 10_000 and device["idle_share"] < 0.01
    (step,) = got["modules"].values()
    assert step["executions"] == 1  # the first and last may be cut
    assert step["seconds_per_execution"] == pytest.approx(0.12989, rel=1e-3)
    assert step["busy_s_per_execution"] == pytest.approx(0.12988, rel=1e-3)
    assert got["device_ops"][0][0].startswith("%fusion.1 ")  # palette expand
    assert any("tpu_custom_call" in n for n, _ in got["device_ops"])
    assert got["idle_gaps"][0][0] == "bench.wait"  # the probe's own span
