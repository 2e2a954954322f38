"""``chip_smoke.py``'s contract with whoever runs it: what it prints and
how it exits. (Its phases are rehearsed at tiny size in
tests/test_zz_smoke_rehearsal.py; what they find on a TPU only a chip run
shows.)"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_without_an_accelerator_it_runs_nothing_and_fails(capsys, monkeypatch):
    """Tier-1 runs on the CPU backend: the script must not train there
    and report success. No phase runs, stdout carries no result, the
    reason goes to stderr, the exit code is not 0."""
    for phase in ("phase_kernels", "phase_headline", "phase_rl",
                  "phase_four_chips"):
        monkeypatch.setattr(
            chip_smoke, phase, lambda *a: pytest.fail("ran a phase on the CPU")
        )
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == ""
    verdict = json.loads(err.strip().splitlines()[-1])
    assert verdict["ok"] is False and verdict["device"]["platform"] == "cpu"


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """The script without the program beside it is not a proof of
    anything: non-zero exit, nothing on stdout."""
    shutil.copy(chip_smoke.__file__, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


def _on_a_v5e(monkeypatch, **phases):
    """main() as it would run on the chip, with stub phases."""
    monkeypatch.setattr(chip_smoke, "device_record", lambda: dict(V5E))
    ran = []

    def stub(name, result):
        def phase(sizes, seed):
            ran.append(name)
            if isinstance(result, Exception):
                raise result
            return {"phase": name, **result}

        phase.__name__ = name
        return phase

    for name in ("phase_kernels", "phase_headline", "phase_rl",
                 "phase_four_chips"):
        monkeypatch.setattr(
            chip_smoke, name, stub(name, phases.get(name, {"seen": 1}))
        )
    return ran


@pytest.mark.usefixtures("compile_cache_config_guard")
def test_last_line_is_the_verdict_and_the_device(capsys, monkeypatch):
    ran = _on_a_v5e(monkeypatch)
    assert chip_smoke.main(["--seed", "3"]) == 0
    assert ran == ["phase_kernels", "phase_headline", "phase_rl"]
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-1] == json.dumps({"ok": True, "device": V5E})
    start = json.loads(lines[0])
    assert start["phase"] == "start" and start["compile_cache_dir"]
    assert [json.loads(ln)["ok"] for ln in lines[1:-2]] == [True] * 3
    assert set(json.loads(lines[-2])["compile_cache"]) == {"hits", "misses"}


@pytest.mark.usefixtures("compile_cache_config_guard")
def test_a_failed_phase_fails_the_run_but_the_rest_still_runs(
    capsys, monkeypatch
):
    ran = _on_a_v5e(
        monkeypatch, phase_headline=chip_smoke.SmokeFailure("loss rose")
    )
    assert chip_smoke.main([]) == 1
    assert ran == ["phase_kernels", "phase_headline", "phase_rl"]
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines[-1] == {"ok": False, "device": V5E}
    failed = [ln for ln in lines if ln.get("phase") == "phase_headline"]
    assert failed and failed[0]["ok"] is False
    assert "loss rose" in failed[0]["error"]


@pytest.mark.usefixtures("compile_cache_config_guard")
def test_four_chip_option_runs_that_path_and_no_other(capsys, monkeypatch):
    ran = _on_a_v5e(monkeypatch)
    monkeypatch.setattr(
        chip_smoke, "device_record", lambda: {**V5E, "count": 4}
    )
    assert chip_smoke.main(["--four-chips"]) == 0
    assert ran == ["phase_four_chips"]
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {**V5E, "count": 4}}


def test_a_chip_without_a_peak_on_record_is_an_error(monkeypatch):
    monkeypatch.setattr(
        chip_smoke, "device_record",
        lambda: {"platform": "tpu", "kind": "TPU v9 mystery", "count": 1},
    )
    with pytest.raises(KeyError, match="TPU v9 mystery"):
        chip_smoke.main([])


@pytest.mark.parametrize("tile", sorted(chip_smoke.TILE_CAPACITY))
def test_tile_capacity_is_the_cube_scene_s_measured_fit(tile):
    """The capacity pinned for a geometry holds the most tiles the cube
    scene changes in one frame at ``REAL.shape`` and is that count
    rounded up to 32: under it the stream grows mid-run and the decode
    compiles again, far over it the wire carries padding."""
    from blendjax.ops.tiles import TileDeltaEncoder
    from blendjax.producer.sim import CubeScene

    shape = chip_smoke.REAL.shape
    most = 0
    for seed in (0, 1):
        scene = CubeScene(shape=shape, seed=seed)
        enc = TileDeltaEncoder(scene.background_image(), tile=tile)
        frame = np.empty((*shape, 4), np.uint8)
        for f in range(1, 1001):
            scene.step(f)
            scene.render(out=frame)
            most = max(most, len(enc.encode(frame)[0]))
    capacity = int(chip_smoke.TILE_CAPACITY[tile])
    assert capacity % 32 == 0 and capacity - 32 < most <= capacity


def test_no_environment_variable_changes_the_real_sizes():
    """What the chip streams is what the file says: the knobs that used
    to reach it through the old benchmark's module change nothing."""
    env = dict(os.environ)
    env["BLENDJAX_" + "BENCH_CHUNK"] = "2"
    env["BLENDJAX_" + "BENCH_TILE"] = "32x32"
    out = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke as c, dataclasses, json;"
         "print(json.dumps(dataclasses.asdict(c.REAL)))"],
        cwd=chip_smoke.ROOT, env=env, check=True, capture_output=True,
        text=True, timeout=120,
    ).stdout
    real = json.loads(out)
    assert (real["shape"], real["batch"], real["chunk"]) == ([480, 640], 8, 16)
    assert (real["tile_capacity"], real["tile_pal_bits"]) == ("160", "4")
    assert real["former"] == dict(
        patch=20, dim=512, depth=8, num_heads=4, num_outputs=16
    )


def test_nothing_names_the_deleted_benchmark():
    """The first benchmark's module and its environment knobs are gone
    (PR 32): no code, test, example or script imports the one or reads
    the others."""
    gone = re.compile("BLENDJAX_" + r"BENCH_|^\s*(import|from) bench\b", re.M)
    found = []
    files = [os.path.join(chip_smoke.ROOT, "chip_smoke.py")]
    for top in ("blendjax", "tests", "examples", "scripts"):
        for base, _, names in os.walk(os.path.join(chip_smoke.ROOT, top)):
            files += [os.path.join(base, n) for n in names if n.endswith(".py")]
    for path in files:
        with open(path, encoding="utf-8") as f:
            if gone.search(f.read()):
                found.append(os.path.relpath(path, chip_smoke.ROOT))
    assert not found
