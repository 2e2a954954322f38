"""The work one optimizer update *requires* of the held routed experts of
a ``StreamHybrid``-shaped model (every ``E`` layer), whatever implements
them: the two products ``W_down relu(W_up x)^2`` over the (token, expert)
rows EXPECTED here and the held weights once.

- rows: of a token's ``experts_per_token`` picks, ``experts_held /
  num_experts`` are expected on the experts held here (9,600 tokens x 6 x
  8 / 128 = 3,600 an update). An implementation that computes an expert
  over tokens that did not choose it, or a seed whose routing sends this
  chip more, spends more time on the same required work;
- forward: two products, ``2 x rows x C x F`` operations each; backward:
  four (an input and a weight gradient for each);
- bytes, in the compute type, each once: forward reads the rows and both
  stacked weights and writes the hidden rows and the result; backward
  reads them and the result's gradient and writes the rows' and the
  weights' gradients. The router, the gather and the combine are not the
  experts' (``moe.route_device_ms_per_update`` reads them).
"""

BYTES = {"bf16": 2, "f32": 4}


def required(kwargs: dict, input_shape, batch: int, precision: str,
             which: str) -> dict:
    """``{"flops", "bytes"}`` an update requires of all ``E`` layers' held
    experts at ``batch`` images; ``which``: ``forward``, ``backward`` or
    ``train`` (both)."""
    h, w, _c = input_shape
    tokens = batch * (h // kwargs["patch"]) * (w // kwargs["patch"])
    held = kwargs.get("experts_held") or kwargs["num_experts"]
    rows = tokens * kwargs["experts_per_token"] * held / kwargs["num_experts"]
    c, f = kwargs["dim"], kwargs["expert_width"]
    layers = kwargs["pattern"].count("E")
    size = BYTES[precision]
    weights, acts = 2 * held * c * f * size, rows * (2 * c + 2 * f) * size
    forward = {"flops": 2 * 2 * rows * c * f, "bytes": weights + acts}
    backward = {"flops": 2 * forward["flops"],
                "bytes": 2 * (weights + acts)}
    parts = {"forward": [forward], "backward": [backward],
             "train": [forward, backward]}[which]
    return {
        k: layers * sum(part[k] for part in parts) for k in ("flops", "bytes")
    }
