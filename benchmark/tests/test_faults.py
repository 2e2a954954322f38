"""The comparison that decides ``correct`` has to fail what it exists to
catch. At the rehearsal's size on the CPU: the control (the reference in
float8 weights, the nearest precision under the configuration's bf16) and
each fault planted in the reference read over the rehearsal's bar, sound
runs read under it; and a whole run, with the timed path broken underneath
the harness, prints ``correct`` false. The chip's readings at the cells'
own size are in PERF.md (Findings, PR 27); ``no_exchange`` reads under the
bar at every size tried and is listed there as what the losses alone cannot
catch: its test is a strict ``xfail``, a defect on record and not a behaviour."""

import json
import os
import subprocess
import sys

import pytest

import cells
import controls

DRIVER = """
import sys
sys.path[:0] = [{root!r}, {bench!r}, {tests!r}]
import controls
controls.plant({fault!r})
import run
sys.exit(run.main({argv!r}))
"""


@pytest.fixture(scope="module")
def rehearsal_readings(tmp_path_factory):
    out = cells.OUT
    cells.OUT = str(tmp_path_factory.mktemp("out"))
    try:
        cell = cells.Cell("vitb16_replay", rehearse=True)
        return cell.config["reference_check"]["rtol"], [
            controls.readings(cell, seed) for seed in (21, 22, 23)
        ]
    finally:
        cells.OUT = out


@pytest.mark.parametrize("fault", ["fp8_weights", "state_unchanged", "half_batch"])
def test_the_control_and_the_faults_read_over_the_bar(rehearsal_readings, fault):
    rtol, seeds = rehearsal_readings
    assert min(r[fault] for r in seeds) > 1.5 * rtol


def planted_run(fault, workload="vitb16_replay", devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cells.ROOT, ".xla_cache")
    argv = ["--workload", workload, "--seed", "3", "--seconds", "0.5",
            "--trace", "0", "--rehearse"]
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER.format(
            root=cells.ROOT, bench=cells.HERE,
            tests=os.path.dirname(os.path.abspath(__file__)),
            fault=fault, argv=argv,
        )], cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    if proc.returncode:  # not an assert: the strict xfail below expects one
        pytest.fail(proc.stderr[-3000:])
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("fault, workload, devices", [
    ("state_unchanged", "vitb16_replay", 1),
    ("half_batch", "vitb16_replay", 1),
    ("loss_altered", "vitb16_replay", 1),
    ("half_batch", "vitb16_mesh4", 4),
])
def test_a_run_over_a_broken_timed_path_is_not_correct(fault, workload, devices):
    line, stderr = planted_run(fault, workload, devices)
    assert line["correct"] is False
    value, limit = line["compared"]["loss_rel_diff"]
    assert value > limit
    assert line["compared"]["seeded_leaves_differing"] == [0, 0]
    assert "failed check reference" in stderr


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=(
    "the first 2 losses hardly see the gradient: PERF.md, Open questions, first"
))
def test_a_run_without_the_gradient_exchange_is_not_correct():
    """A gradient exchange left out (the whole batch's loss, one chip's
    quarter's gradient) moves the second loss by less than bf16 does, so the
    run stays ``correct``: a defect of the accepted comparison, which this
    test expects to fail until the comparison reads the gradient. The day
    it does, strict xfail turns the repair into a red test to take the mark
    off."""
    line, _ = planted_run("no_exchange", "vitb16_mesh4", 4)
    value, limit = line["compared"]["loss_rel_diff"]
    assert value > limit and line["correct"] is False
