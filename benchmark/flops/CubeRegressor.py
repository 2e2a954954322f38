"""Required operations of CubeRegressor per image: 3x3 stride-2 SAME
convolutions, global pool, Dense(256), Dense(2 * num_points)."""


def forward_flops(kwargs: dict, input_shape) -> dict:
    h, w, c = input_shape
    out = {}
    for i, f in enumerate(kwargs.get("features", (32, 64, 128, 256))):
        h, w = -(-h // 2), -(-w // 2)
        out[f"conv{i}"] = 2 * h * w * 9 * c * f
        c = f
    out["dense"] = 2 * c * 256 + 2 * 256 * 2 * kwargs.get("num_points", 8)
    return out


def train_flops_per_image(kwargs: dict, input_shape) -> float:
    f = forward_flops(kwargs, input_shape)
    # backward = 2x forward; the first convolution needs no input gradient
    return 3 * sum(f.values()) - f["conv0"]
