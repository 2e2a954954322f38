"""Every cell end to end on the CPU at its tiny ``rehearse`` size: real
producer children, the real pipeline and drivers, the reference check,
the window, the last line. And the proof that a later PR edits nothing:
a throw-away configuration, traffic mix, cell and layer metric added as
new files only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import cells

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CANDIDATES = os.path.join(cells.HERE, "candidates", "cube_cells.json")


def run(args, root=cells.ROOT, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".xla_cache")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "workload, devices, extra",
    [
        ("vitb16_live", 1, []),
        ("vitb16_replay", 1, []),
        ("vitb16_mesh4", 4, []),
        ("cube_live", 1, ["--benchmark-json", CANDIDATES]),
        ("cube_replay", 1, ["--benchmark-json", CANDIDATES]),
    ],
)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(workload, devices, extra, trace):
    line = last_line(run(
        ["--workload", workload, "--seed", "3", "--seconds", "2",
         "--trace", str(trace), "--rehearse", *extra],
        devices=devices,
    ))
    assert KEYS <= set(line) and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    # counts only: a CPU run prints no time, rate, share or utilisation
    assert set(line["metrics"]) <= {"wire.bytes_per_img"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_without_an_accelerator_nothing_is_printed():
    proc = run(["--workload", "vitb16_replay", "--seed", "0",
                "--seconds", "1", "--trace", "0"])
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "nothing was run" in proc.stderr


def test_a_new_cell_is_new_files_only(tmp_path):
    """Copy the benchmark, ADD a configuration, a traffic mix, a layer
    metric with a reader of its own and a cell, edit no file that was
    there, and see run.py report the new metric in the new cell."""
    root = str(tmp_path)
    shutil.copytree(
        cells.HERE, os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    bench = os.path.join(root, "benchmark")
    before = {
        os.path.join(b, f): os.path.getmtime(os.path.join(b, f))
        for b, _d, fs in os.walk(bench) for f in fs
    }
    cfg = cells.load_json("configs", "cube_cnn.json")
    cfg.update(name="throwaway_cnn")
    cfg["model"]["kwargs"] = {"features": [8, 16]}
    with open(os.path.join(bench, "configs", "throwaway_cnn.json"), "w") as f:
        json.dump(cfg, f)
    traffic = cells.load_json("traffic", "replay_tile.json")
    traffic["rehearse"]["messages"] = 4
    with open(os.path.join(bench, "traffic", "throwaway_replay.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "readers", "throwaway_reader.py"), "w") as f:
        f.write("def read(obs, scale):\n"
                "    return scale * obs['window']['images_handed']\n")
    with open(
        os.path.join(bench, "layer_metrics", "throwaway.images.json"), "w"
    ) as f:
        json.dump({"reader": "throwaway_reader", "args": {"scale": 2}}, f)
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        listing = json.load(f)
    listing["configs"].append({
        "name": "throwaway_cnn", "source": cfg["source"],
        "file": "benchmark/configs/throwaway_cnn.json", "reduced": [],
        "why": "test",
    })
    listing["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway_cnn",
        "traffic": "throwaway_replay", "chips": 1, "why": "test",
    })
    listing["per_layer"].append({
        "name": "throwaway.images", "unit": "img", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "img_per_s_per_chip", "workloads": ["throwaway_cell"],
    })
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(listing, f)

    proc = run(
        ["--workload", "throwaway_cell", "--seed", "5", "--seconds", "1",
         "--trace", "1", "--rehearse"], root=root,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(s) for s in proc.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True
    # the rehearsal's last line keeps counts only; the detail file has all
    with open(os.path.join(
        bench, "out", "runs", "throwaway_cell-s5-t1.json"
    )) as f:
        detail = json.load(f)
    assert detail["per_layer"]["throwaway.images"] == 2 * lines[-1]["attempted"]
    for path, mtime in before.items():
        assert os.path.getmtime(path) == mtime, f"{path} was edited"


def test_recordings_are_reused_and_the_oldest_go(monkeypatch, tmp_path):
    """A second run with the same seed finds its recording; a checkout
    keeps only the newest few, however many seeds it is run with."""
    monkeypatch.setattr(cells, "OUT", str(tmp_path))
    monkeypatch.setattr(cells, "KEEP_RECORDINGS", 2)
    cell = cells.Cell("vitb16_replay", rehearse=True)
    first = cell.ensure_recording(1, 2)
    stamp = os.path.getmtime(first)
    assert cell.ensure_recording(1, 2) == first
    assert os.path.getmtime(first) == stamp  # not made again
    cell.ensure_recording(2, 2)
    last = cell.ensure_recording(3, 2)
    left = os.listdir(os.path.dirname(last))
    assert len(left) == 2 and os.path.basename(last) in left
    assert os.path.basename(first) not in left
