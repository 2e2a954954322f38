"""Required operations of StreamFormer (a ViT encoder) per image."""


def forward_flops(kwargs: dict, input_shape) -> dict:
    h, w, c = input_shape
    p, d, depth = kwargs["patch"], kwargs["dim"], kwargs["depth"]
    t = (h // p) * (w // p)
    if kwargs.get("num_experts"):
        raise NotImplementedError("MoE blocks are not counted here")
    block = (
        2 * t * d * 3 * d      # q, k, v projections
        + 2 * t * t * d        # scores, all heads
        + 2 * t * t * d        # probabilities x values
        + 2 * t * d * d        # output projection
        + 2 * 2 * t * d * 4 * d  # MLP, ratio 4, two products
    )
    return {
        "patch_embed": 2 * t * (p * p * c) * d,
        "blocks": depth * block,
        "head": 2 * d * kwargs.get("num_outputs", 16),
    }


def train_flops_per_image(kwargs: dict, input_shape) -> float:
    f = forward_flops(kwargs, input_shape)
    # backward = 2x forward; the patch embedding needs no input gradient
    return 2 * f["patch_embed"] + 3 * (f["blocks"] + f["head"])
