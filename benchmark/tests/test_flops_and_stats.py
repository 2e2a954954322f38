"""The benchmark's own arithmetic against hand counts."""

import math

import pytest

import cells
import flops
import stats


def test_vit_b16_at_1200_tokens_against_a_hand_count():
    cell = cells.Cell("vitb16_live")
    t, d = 30 * 40, 768
    assert cell.shape == (480, 640) and t == 1200
    block = (
        2 * t * d * 2304          # qkv: 4.247 GFLOP
        + 2 * 2 * t * t * d       # scores and probabilities x values: 4.424
        + 2 * t * d * d           # projection: 1.416
        + 2 * 2 * t * d * 3072    # MLP: 11.325
    )
    patch = 2 * t * (16 * 16 * 4) * d
    forward = patch + 12 * block + 2 * d * 16
    assert forward == pytest.approx(258.8e9, rel=2e-3)   # ~257 + patch embed
    # backward = 2 x forward, less the patch embedding's input gradient
    assert flops.train_flops_per_image(cell) == pytest.approx(
        3 * forward - patch
    )
    assert flops.train_flops_per_image(cell) == pytest.approx(772e9, rel=1e-2)


def test_cube_regressor_against_a_hand_count():
    cell = cells.Cell(
        "cube_replay",
        benchmark_json=cells.HERE + "/candidates/cube_cells.json",
    )
    convs = [
        2 * 240 * 320 * 9 * 4 * 32,    # 176.9 MFLOP
        2 * 120 * 160 * 9 * 32 * 64,   # 707.8
        2 * 60 * 80 * 9 * 64 * 128,    # 707.8
        2 * 30 * 40 * 9 * 128 * 256,   # 707.8
    ]
    dense = 2 * 256 * 256 + 2 * 256 * 16
    forward = sum(convs) + dense
    assert forward == pytest.approx(2.300e9, rel=1e-3)
    assert flops.train_flops_per_image(cell) == pytest.approx(
        3 * forward - convs[0]
    )


def test_mfu_and_peaks():
    # 100 img/s x 776 GFLOP on one v5e = 39.4 % of 197 TFLOP/s
    assert flops.mfu(100.0, 776e9, 1, "TPU v5 lite") == pytest.approx(
        0.3939, rel=1e-3
    )
    assert flops.mfu(400.0, 776e9, 4, "TPU v5 lite") == pytest.approx(
        0.3939, rel=1e-3
    )
    with pytest.raises(KeyError):
        flops.peak("TPU v99")
    with pytest.raises(KeyError):
        flops.peak("_what")


@pytest.mark.parametrize(
    "values, q, want",
    [
        ([1, 2, 3, 4, 5], 50, 3.0),
        ([5, 1, 4, 2, 3], 50, 3.0),
        ([1, 2, 3, 4], 50, 2.5),
        ([1, 2, 3, 4], 95, 3.85),
        ([7], 95, 7.0),
        ([0, 10], 25, 2.5),
    ],
)
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_refuses_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_frame_ages_take_first_stamps_inside_the_window():
    def rec(publish, retire, extra=()):
        return {"stages": [
            ["publish", publish, 0.0], *extra, ["step_retire", retire, 0.0],
        ]}

    records = [
        rec(10.0, 10.5),
        rec(10.2, 11.0, extra=[["recv", 10.3, 0.0], ["recv", 10.9, 0.0]]),
        rec(9.0, 9.9),                 # retired before the window
        rec(11.5, 12.6),               # retired after it
        {"stages": [["publish", 10.0, 0.0]]},  # never retired
    ]
    assert stats.frame_ages_ms(records, 10.0, 12.0) == pytest.approx(
        [500.0, 800.0]
    )
    assert stats.frame_ages_ms(
        records, 10.0, 12.0, first="publish", last="recv"
    ) == pytest.approx([100.0])
    # frames published while the consumer was still setting up are out
    assert stats.frame_ages_ms(
        records, 10.0, 12.0, published_after=10.1
    ) == pytest.approx([800.0])


def test_attempted_and_failed():
    attempted, failed = stats.attempted_failed(
        images_handed=1024, batch=8, seq_gaps=2, torn_messages=1,
        dropped_messages=0, losses=[0.1, math.nan, 0.2, math.inf],
    )
    assert attempted == 1024
    assert failed == (2 + 1) * 8 + 2 * 8
    assert stats.attempted_failed(
        images_handed=64, batch=8, seq_gaps=0, torn_messages=0,
        dropped_messages=0, losses=[0.1] * 8,
    ) == (64, 0)
