"""Model FLOP/s utilisation end to end: required forward+backward
operations per image (from shapes, no recomputed work) x images/s over
chips x the chip's bf16 peak. Not a kernel's roofline share."""

import flops


def read(obs):
    w = obs["window"]
    if obs["device"]["platform"] == "cpu":  # no chip, no peak, no utilisation
        return None
    return 100.0 * flops.mfu(
        w["images"] / w["seconds"], obs["flops_per_image"], w["chips"],
        obs["device"]["kind"],
    )
