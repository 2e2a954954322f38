"""``readers/counter_ratio.py`` and the two metrics of the expert
layer's pick counts that read the program's counters."""

import pytest

import cells

READER = cells.load_module("readers", "counter_ratio")
COUNTS = {
    "moe.rows_held": 4_000, "moe.rows_busiest_share": 9_000,
    "moe.rows_even_share": 3_600, "wire.seq_gaps": 0,
}


def obs(counters, images=8):
    return {"counters": counters, "window": {"images_handed": images}}


@pytest.mark.parametrize("numerator, denominator, want", [
    (["moe.rows_busiest_share"], ["moe.rows_even_share"], 2.5),
    (["moe.rows_held", "moe.rows_busiest_share"], ["moe.rows_even_share"],
     13_000 / 3_600),
    (["moe.rows_even_share"], ["moe.rows_even_share"], 1.0),
])
def test_reads_a_ratio(numerator, denominator, want):
    assert READER.read(obs(COUNTS), numerator, denominator) == (
        pytest.approx(want)
    )


@pytest.mark.parametrize("numerator, denominator", [
    (["moe.rows_busiest_share"], ["moe.absent"]),
    (["moe.absent"], ["moe.rows_even_share"]),
    (["moe.rows_busiest_share"], ["wire.seq_gaps"]),  # a zero below
])
def test_reads_none_where_a_side_is_missing(numerator, denominator):
    assert READER.read(obs(COUNTS), numerator, denominator) is None


def test_the_parent_reads_nothing_for_either_metric():
    """A program that books no pick counts (the parent commit's, a
    vit_b16 cell's) leaves both metrics out of the line."""
    for name in ("moe.held_rows_per_image", "moe.busiest_over_even"):
        spec = cells.load_json("layer_metrics", f"{name}.json")
        reader = cells.load_module("readers", spec["reader"])
        assert reader.read(
            obs({"wire.seq_gaps": 0}), **spec["args"]
        ) is None
        assert reader.read(obs(COUNTS), **spec["args"]) is not None


def test_held_rows_per_image_at_an_even_load():
    """nemotron3nano_replay: 4 expert layers, 1,200 tokens an image, 6
    picks a token, 8 of 128 experts held: 1,800 rows an image."""
    spec = cells.load_json("layer_metrics", "moe.held_rows_per_image.json")
    reader = cells.load_module("readers", spec["reader"])
    images = 64
    held = images * 4 * 1_200 * 6 * 8 // 128
    assert reader.read(
        obs({"moe.rows_held": held}, images), **spec["args"]
    ) == 1_800
