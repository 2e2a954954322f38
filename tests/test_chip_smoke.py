"""``chip_smoke.py``'s contract with whoever runs it: what it prints and
how it exits. (Its phases are rehearsed at tiny size in
tests/test_zz_smoke_rehearsal.py; what they find on a TPU only a chip run
shows.)"""

import json
import os
import shutil
import subprocess
import sys

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
