"""BENCHMARK.json against the limits of the benchmark's contract that
can be checked without a run, and against the files it names."""

import json
import os
import re

import pytest

import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATHNAME = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
FILES = [
    os.path.join(cells.ROOT, "BENCHMARK.json"),
    os.path.join(cells.HERE, "candidates", "cube_cells.json"),
    os.path.join(cells.HERE, "candidates", "streamformer_d2048_l9.json"),
]


def line(s, limit=200):
    return isinstance(s, str) and 1 <= len(s) <= limit and not re.search(
        r"[\n\t]", s
    )


@pytest.fixture(params=FILES, ids=["BENCHMARK.json", "cube_cells", "probe"])
def bench(request):
    with open(request.param) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return {k: v for k, v in json.loads(raw).items() if k != "_what"}


def test_top_level_keys_and_command(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATHNAME.match(p) and ".." not in p for p in bench["paths"])
    assert 1 <= len(bench["command"]) <= 32
    assert all(line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert PATHNAME.match(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
        for k in c["reduced"]:  # no width is ever named
            assert not re.search(
                r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head|"
                r"expan|experts_per", k
            )


def test_workloads(bench, request):
    cells_ = bench["workloads"]
    # the probe is one cell and never admitted; what can be admitted has two
    fewest = 1 if request.node.callspec.id == "probe" else 2
    assert fewest <= len(cells_) <= 24
    assert len({w["name"] for w in cells_}) == len(cells_)
    assert len({(w["config"], w["traffic"]) for w in cells_}) == len(cells_)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells_:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        assert os.path.exists(
            os.path.join(cells.HERE, "traffic", w["traffic"] + ".json")
        )
    four = sum(1 for w in cells_ if w["chips"] == 4)
    assert four <= max(1, len(cells_) // 4)


def test_metrics(bench):
    cell_names = {w["name"] for w in bench["workloads"]}
    e2e, per = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"
        }
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in per:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"
        }
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in {e["name"] for e in e2e}
        spec = cells.load_json("layer_metrics", m["name"] + ".json")
        assert os.path.exists(
            os.path.join(cells.HERE, "readers", spec["reader"] + ".py")
        )
    for m in e2e + per:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m.get("workloads", True)
    for w in cell_names:
        mine = lambda ms: [  # noqa: E731
            m for m in ms if "workloads" not in m or w in m["workloads"]
        ]
        reported = {m["name"] for m in mine(e2e)}
        assert "setup_s" in reported and len(reported) >= 2
        assert mine(per)
        for m in mine(per):  # a layer metric only where what it moves is
            assert m["moves"] in reported


def test_every_benchmark_file_is_named_from_allowed_characters():
    for base, _dirs, files in os.walk(cells.HERE):
        if os.sep + "out" in base or "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), cells.ROOT)
            assert PATHNAME.match(rel), rel
