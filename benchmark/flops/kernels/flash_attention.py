"""The work one optimizer update *requires* of the attention core of a
``StreamFormer``-shaped model, whatever implements it: every layer's
softmax(Q K^T / sqrt(D)) V over the tokens the input has.

Counted from the configuration's model arguments, the input shape and the
images a chip sees an update; 2 operations a multiply-add:

- one T x T x D product a head is ``2 * B * H * T * T * D`` (``vit_b16``
  at batch 8: 2 x 8 x 12 x 1,200 x 1,200 x 64 = 17.69 GFLOP a layer);
- forward: two products (scores; probabilities x values);
- backward: four (dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q). A
  kernel that recomputes the scores runs a fifth: its choice, not counted;
- the tokens are the input's (1,200), never what a kernel pads them to
  (1,280); a configuration whose model arguments say ``causal`` counts the
  lower triangle, T (T + 1) / 2 of T x T. No configuration does yet: the
  branch is here because the PR that brings one may not edit this file;
- bytes: each operand and result once, in the compute type: forward reads
  q, k, v and writes o; backward reads q, k, v, o, dO and writes dQ, dK, dV.
  Saved statistics (a log-sum-exp) are an implementation's and not counted.

Softmax's exponentials and sums are not matrix work and are left out, as
in ``flops/<model>.py``: this is the numerator of a share of the chip's
peak, and must never be counted high.
"""

PRODUCTS = {"forward": 2, "backward": 4}
TENSORS = {"forward": 4, "backward": 8}
BYTES = {"bf16": 2, "f32": 4}


def shape(kwargs: dict, input_shape, batch: int) -> dict:
    h, w, _c = input_shape
    p, d, heads = kwargs["patch"], kwargs["dim"], kwargs["num_heads"]
    return {
        "batch": batch, "tokens": (h // p) * (w // p), "heads": heads,
        "head_dim": d // heads, "layers": kwargs["depth"],
        "causal": bool(kwargs.get("causal", False)),
    }


def required(kwargs: dict, input_shape, batch: int, precision: str,
             which: str) -> dict:
    """``{"flops", "bytes"}`` an update requires of all layers' ``which``
    (``forward`` or ``backward``) attention cores at ``batch`` images."""
    s = shape(kwargs, input_shape, batch)
    t = s["tokens"]
    pairs = t * (t + 1) // 2 if s["causal"] else t * t
    product = 2 * s["batch"] * s["heads"] * pairs * s["head_dim"]
    tensor = (
        s["batch"] * t * s["heads"] * s["head_dim"] * BYTES[precision]
    )
    return {
        "flops": s["layers"] * PRODUCTS[which] * product,
        "bytes": s["layers"] * TENSORS[which] * tensor,
    }
