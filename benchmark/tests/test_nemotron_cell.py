"""The cell ``nemotron3nano_replay`` is files and entries: found by name,
counted right, rehearsed end to end on the CPU, and what the catalog's row
publishes is what its configuration file states."""

import json
import os

import pytest

import cells
import flops
from test_rehearse import KEYS, last_line, run

CELL, CONFIG = "nemotron3nano_replay", "nemotron3_nano_30b_a3b"
NEW_METRICS = {
    "ssm.device_ms_per_update", "ssd.core_device_ms_per_update",
    "moe.device_ms_per_update", "moe.route_device_ms_per_update",
    "ssd_scan_roofline", "moe_experts_roofline",
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    line = last_line(run(
        ["--workload", CELL, "--seed", "3", "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
    ))
    assert KEYS <= set(line) and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) <= {"wire.bytes_per_img"}
    value, limit = line["compared"]["loss_rel_diff"]
    assert value < limit


def test_its_files_are_found_by_name():
    cell = cells.Cell(CELL)
    assert cell.chips == 1 and cell.batch == 8 and cell.chunk == 4
    assert cell.model_class() == "StreamHybrid"
    assert cell.config["name"] == CONFIG
    assert type(cell.model()).__name__ == "StreamHybrid"
    reference = cells.load_module("references", "StreamHybrid")
    assert callable(reference.forward)
    with open(reference.__file__) as f:  # the reference stands alone
        source = f.read()
    assert "import blendjax" not in source and "from blendjax" not in source
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert NEW_METRICS <= mine
    assert {"step.mfu", "device.hbm_peak_gb", "attn.core_device_ms_per_update",
            "step.device_ms_per_update"} <= mine
    for name in NEW_METRICS:
        spec = cells.load_json("layer_metrics", f"{name}.json")
        assert callable(cells.load_module("readers", spec["reader"]).read)


def test_compile_rehearsal_lists_it():
    """``compile_rehearsal.py`` without ``--workload`` takes its cells from
    BENCHMARK.json; the compile itself (45 s here for this cell) is run by
    hand before a chip call, PERF.md has its reading."""
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    assert CELL in names


def test_required_operations_are_the_issues_count():
    cell = cells.Cell(CELL)
    kwargs, shape = cell.config["model"]["kwargs"], (*cell.shape, 4)
    parts = cells.load_module("flops", "StreamHybrid").forward_flops(
        kwargs, shape
    )
    forward = parts["patch_embed"] + sum(parts["layers"].values()) + parts[
        "head"]
    assert 0.68e12 < forward < 0.70e12          # 0.69 TFLOP an image
    assert 2.05e12 < flops.train_flops_per_image(cell) < 2.08e12
    share = {k: v / forward for k, v in parts["layers"].items()}
    assert 0.55 < share["M"] < 0.57 and 0.32 < share["E"] < 0.34
    ssd = cells.load_module("flops/kernels", "ssd_scan").required(
        kwargs, shape, 8, "bf16", "train"
    )
    # 4 layers x 9,600 tokens x 64 heads x 3 multiply-adds x 64 x 128,
    # forward, and twice that backward
    assert ssd["flops"] == 3 * 4 * 9600 * 64 * 3 * 2 * 64 * 128
    experts = cells.load_module("flops/kernels", "moe_experts").required(
        kwargs, shape, 8, "bf16", "train"
    )
    # 3,600 expected rows a layer, two products, forward + 2 x backward
    assert experts["flops"] == 3 * 4 * 2 * 2 * 3600 * 2688 * 1856
    assert experts["bytes"] > 0 and ssd["bytes"] > 0


def test_the_configuration_states_the_published_widths():
    with open(os.path.join(cells.HERE, "configs", f"{CONFIG}.json")) as f:
        body = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == body["source"])
        for key, value in row["config"].items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    assert body["layers"] == 9 and body["n_routed_experts"] == 8
    assert body["published"]["n_routed_experts"] == 128
    assert body["published"]["num_hidden_layers"] == 52
    assert body["deployment"]["chips_sharing_an_expert_layer"] == 16
    for key in ("assumed", "departures", "deployment"):
        assert body[key]
    k = body["model"]["kwargs"]
    assert (k["dim"], k["mamba_num_heads"], k["mamba_head_dim"],
            k["ssm_state_size"], k["n_groups"], k["conv_kernel"],
            k["chunk_size"]) == (
        body["hidden_size"], body["mamba_num_heads"], body["mamba_head_dim"],
        body["ssm_state_size"], body["n_groups"], body["conv_kernel"],
        body["chunk_size"])
    assert (k["num_heads"], k["num_kv_heads"], k["head_dim"]) == (
        body["num_attention_heads"], body["num_key_value_heads"],
        body["head_dim"])
    assert (k["num_experts"], k["experts_per_token"], k["expert_width"],
            k["shared_width"], k["routed_scaling"], k["experts_held"]) == (
        body["published"]["n_routed_experts"], body["num_experts_per_tok"],
        body["moe_intermediate_size"],
        body["moe_shared_expert_intermediate_size"],
        body["routed_scaling_factor"], body["n_routed_experts"])
    assert k["pattern"] == body["hybrid_override_pattern"] == (
        body["published"]["hybrid_override_pattern"][:9])
    assert k["norm_eps"] == body["layer_norm_epsilon"]
