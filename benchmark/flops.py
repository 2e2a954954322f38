"""Operations a cell's model requires, from shapes.

``train_flops_per_image`` is the forward and backward passes' matrix
and convolution products for one image (2 per multiply-add), counted by
``flops/<model class>.py`` from the model's arguments and the input
shape. The backward pass costs twice the forward's (a product for the
input gradient, one for the weight gradient), except that the first
layer needs no input gradient. Recomputed work (remat), elementwise
work, softmax and normalisation are not counted: this is the numerator
of a model FLOP/s utilisation, not what the chip executed.

``peak`` reads ``peaks.json``; a device kind without an entry raises.
"""

from __future__ import annotations

import cells


def train_flops_per_image(cell) -> float:
    mod = cells.load_module("flops", cell.model_class())
    return float(mod.train_flops_per_image(
        cell.config["model"]["kwargs"], (*cell.shape, cell.channels)
    ))


def peak(device_kind: str) -> dict:
    table = cells.load_json("peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peak on record for device kind {device_kind!r}: add it to "
            "benchmark/peaks.json with its source"
        )
    return table[device_kind]


def mfu(img_per_s: float, flops_per_image: float, chips: int,
        device_kind: str) -> float:
    """Required FLOP/s over ``chips`` x the chip's bf16 peak."""
    return img_per_s * flops_per_image / (
        chips * peak(device_kind)["bf16_flops_per_s"]
    )
