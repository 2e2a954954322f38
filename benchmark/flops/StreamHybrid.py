"""Required operations of StreamHybrid (a stack by pattern string: Mamba-2
mixers ``M``, expert layers ``E``, grouped-query attention ``*``) per
image: matrix and convolution products and the state-space recurrence's
own multiply-adds, 2 operations a multiply-add. What an implementation
adds of its own is not counted: a chunked scan's quadratic form, a
recomputed layer, an expert computed over tokens that did not choose it.
The routed experts are counted at their EXPECTED load: of a token's
``experts_per_token`` picks, ``experts_held / num_experts`` land on the
experts held here."""


def tokens(kwargs: dict, input_shape) -> int:
    h, w, _c = input_shape
    return (h // kwargs["patch"]) * (w // kwargs["patch"])


def expected_rows(kwargs: dict, n_tokens: int) -> float:
    """(token, expert) pairs a chip's held experts are expected to get."""
    held = kwargs.get("experts_held") or kwargs["num_experts"]
    return n_tokens * kwargs["experts_per_token"] * held / kwargs["num_experts"]


def ssd_recurrence_flops(kwargs: dict, n_tokens: int) -> int:
    """A token a head: decay the P x N state, add ``dt x (x) B``, read it
    out against ``C``: three multiply-adds a state element."""
    return n_tokens * kwargs["mamba_num_heads"] * 3 * 2 * (
        kwargs["mamba_head_dim"] * kwargs["ssm_state_size"]
    )


def forward_flops(kwargs: dict, input_shape) -> dict:
    _h, _w, c = input_shape
    t, d, p = tokens(kwargs, input_shape), kwargs["dim"], kwargs["patch"]
    inner = kwargs["mamba_num_heads"] * kwargs["mamba_head_dim"]
    bc = 2 * kwargs["n_groups"] * kwargs["ssm_state_size"]
    mamba = (
        2 * t * d * (2 * inner + bc + kwargs["mamba_num_heads"])  # in_proj
        + 2 * t * kwargs.get("conv_kernel", 4) * (inner + bc)     # conv
        + ssd_recurrence_flops(kwargs, t)
        + 2 * t * inner * d                                       # out_proj
    )
    experts = (
        2 * t * d * kwargs["num_experts"]                         # router
        + 2 * 2 * t * d * kwargs.get("shared_width", 0)           # shared
        + 2 * 2 * expected_rows(kwargs, t) * d * kwargs["expert_width"]
    )
    q = kwargs["num_heads"] * kwargs["head_dim"]
    kv = kwargs["num_kv_heads"] * kwargs["head_dim"]
    attention = (
        2 * t * d * (q + 2 * kv)                                  # q, k, v
        + 2 * 2 * kwargs["num_heads"] * (t * (t + 1) // 2)
        * kwargs["head_dim"]                                      # causal core
        + 2 * t * q * d                                           # proj
    )
    per_layer = {"M": mamba, "E": experts, "*": attention}
    return {
        "patch_embed": 2 * t * (p * p * c) * d,
        "layers": {
            k: kwargs["pattern"].count(k) * v for k, v in per_layer.items()
        },
        "head": 2 * d * kwargs.get("num_outputs", 16),
    }


def train_flops_per_image(kwargs: dict, input_shape) -> float:
    f = forward_flops(kwargs, input_shape)
    # backward = 2x forward; the patch embedding needs no input gradient
    return 2 * f["patch_embed"] + 3 * (sum(f["layers"].values()) + f["head"])
