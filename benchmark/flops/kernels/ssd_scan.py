"""The work one optimizer update *requires* of the state-space scans of a
``StreamHybrid``-shaped model (every ``M`` layer), whatever implements
them: the recurrence's own multiply-adds and its operands once.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,    y_t = S_t C_t + D x_t

- forward: a token a head decays its P x N state, adds the outer product
  and reads it out: three multiply-adds a state element, 2 operations
  each (``vit``-style count: a chunked form's quadratic products, its
  masks and exponentials are the implementation's, not counted);
- backward: twice the forward's (a gradient for the state's two inputs
  and for its read-out);
- bytes, in the compute type, each once: forward reads x, B, C (the
  groups', not broadcast to heads) and dt (float32) and writes y;
  backward reads those and dy and writes dx, dB, dC, ddt. The state
  never has to leave the chip's fast memory;
- the tokens are the input's (1,200), not what a chunk pads them to.
"""

BYTES = {"bf16": 2, "f32": 4}


def required(kwargs: dict, input_shape, batch: int, precision: str,
             which: str) -> dict:
    """``{"flops", "bytes"}`` an update requires of all ``M`` layers'
    scans at ``batch`` images; ``which``: ``forward``, ``backward`` or
    ``train`` (both)."""
    h, w, _c = input_shape
    rows = batch * (h // kwargs["patch"]) * (w // kwargs["patch"])
    heads, p = kwargs["mamba_num_heads"], kwargs["mamba_head_dim"]
    groups, n = kwargs["n_groups"], kwargs["ssm_state_size"]
    layers = kwargs["pattern"].count("M")
    size = BYTES[precision]
    x, bc, dt = rows * heads * p * size, rows * groups * n * size, rows * heads * 4
    forward = {"flops": rows * heads * 3 * 2 * p * n,
               "bytes": 2 * x + 2 * bc + dt}
    backward = {"flops": 2 * forward["flops"],
                "bytes": 3 * x + 4 * bc + 2 * dt}
    parts = {"forward": [forward], "backward": [backward],
             "train": [forward, backward]}[which]
    return {
        k: layers * sum(part[k] for part in parts) for k in ("flops", "bytes")
    }
