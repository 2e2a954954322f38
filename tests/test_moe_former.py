"""StreamMoEFormer is its plain reference, and its new parts are theirs.

``blendjax.models.StreamMoEFormer`` (sandwich-normed decoder layers:
sliding-window and full grouped-query attention with q/k norms, RoPE and
an output gate; dense SwiGLU layers, then gated routed experts as one
chip's share) at a small size against
``benchmark/references/StreamMoEFormer.py`` (the layers' equations in
plain float32 ``jax.numpy``, which imports nothing of the program) on
seeded weights: the output and every leaf's gradient, on the XLA core and
on the fused kernels (the windowed pair in interpreter mode). The gated
experts' shares are tied to the whole layer, ``MultiHeadAttention``'s new
parts to straight ``jnp``, and ``remat`` changes neither tree nor numbers.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blendjax.models import RoutedExperts, StreamMoEFormer
from blendjax.models.transformer import MultiHeadAttention
from blendjax.parallel.ring import reference_attention
from blendjax.utils.metrics import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 42 tokens a frame (48 x 56 at patch 8) and a window of 16: the sliding
# layers see a band, not everything; heads of 32, four of them, fill the
# fused kernels' 128 lanes
KWARGS = dict(
    patch=8, dim=32, layer_types="SSSFSS", num_dense_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=32, window=16, rope_theta=10000.0,
    dense_width=48, num_experts=16, experts_per_token=4, expert_width=24,
    shared_width=24, routed_scaling=2.826, experts_held=4, expert_offset=4,
    norm_eps=1e-5, num_outputs=16,
)
# float32 against float32: the same sums in another order (a slab of
# keys, a gate over every held expert) through 6 sandwich-normed layers.
# Measured: the output 2e-7 of its largest entry, a leaf's gradient up to
# 1.5e-6 of its largest; the bars are hybrid's, about 10 and 30 times
# that. One bf16 rounding of the q/k norms' statistics moves the output
# by over ten times the output's bar (test_a_lower_precision_fails).
VALUE_TOL, GRAD_TOL = 2e-6, 5e-5
# bf16 compute against the float32 reference through 6 layers: the
# output of O(1) moves by 1e-2 to 3e-2 (measured), so 8e-2, as hybrid's
BF16_TOL = 8e-2


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "reference_stream_moe_former",
        os.path.join(ROOT, "benchmark", "references", "StreamMoEFormer.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _images(batch=2, h=48, w=56, seed=0):
    return jax.random.randint(
        jax.random.key(seed), (batch, h, w, 4), 0, 255
    ).astype(jnp.uint8)


def _seeded(dtype=jnp.float32, **over):
    kwargs = {**KWARGS, **over}
    model = StreamMoEFormer(**kwargs, dtype=dtype)
    params = model.init(jax.random.key(1), _images())["params"]
    # a selection bias that matters: the published init is zeros
    for i in range(kwargs["num_dense_layers"], len(kwargs["layer_types"])):
        params[f"layer{i}"]["ffn"]["e_score_correction_bias"] = (
            0.05 * jax.random.normal(jax.random.key(100 + i), (16,))
        )
    return model, params


def _rel(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _out_and_grad(forward):
    weights = jnp.cos(jnp.arange(16.0))

    def loss(params):
        out = forward(params)
        return jnp.sum(out.astype(jnp.float32) * weights), out

    def both(params):
        (_, out), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return out, grads

    return jax.jit(both)


@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_the_model_is_its_reference(reference, backend):
    model, params = _seeded(attn_backend=backend)
    images = _images()
    got, got_g = _out_and_grad(
        lambda p: model.apply({"params": p}, images)
    )(params)
    want, want_g = _out_and_grad(
        lambda p: reference.forward(p, images, **KWARGS)
    )(params)
    assert got.shape == want.shape == (2, 16)
    assert _rel(got, want) < VALUE_TOL
    leaves = jax.tree_util.tree_leaves_with_path(got_g)
    # 5 (patch embedding, final norm, head) + 6 x (7 attention + 4 norms)
    # + 2 x 3 dense + 4 x 8 experts
    assert len(leaves) == len(jax.tree_util.tree_leaves(want_g)) == 109
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want_g)):
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
        else:
            assert np.abs(np.asarray(w)).max() > 0, name
            assert _rel(g, w) < GRAD_TOL, name


def test_bf16_compute_stays_near_the_float32_reference(reference):
    model, params = _seeded(dtype=jnp.bfloat16)
    images = _images()
    got = jax.jit(lambda p: model.apply({"params": p}, images))(params)
    want = jax.jit(lambda p: reference.forward(p, images, **KWARGS))(params)
    assert got.dtype == jnp.float32  # the head's
    assert _rel(got, want) < BF16_TOL


def test_the_residual_stream_is_float32_under_bf16_compute():
    """The embedding and every layer's output are float32; the sub-blocks
    compute in bf16."""
    model, params = _seeded(dtype=jnp.bfloat16)
    _, state = model.apply({"params": params}, _images(),
                           capture_intermediates=True,
                           mutable=["intermediates"])
    seen = state["intermediates"]
    assert seen["patch_embed"]["__call__"][0].dtype == jnp.float32
    for i in range(len(KWARGS["layer_types"])):
        layer = seen[f"layer{i}"]
        assert layer["__call__"][0].dtype == jnp.float32, i
        assert layer["attn"]["__call__"][0].dtype == jnp.bfloat16, i
        assert layer["ffn"]["__call__"][0].dtype == jnp.bfloat16, i


def test_the_router_reads_a_float32_input_before_its_rounding():
    """Two scores apart by less than bf16 resolves: a float32 input picks
    the larger (expert 1, held here), its bf16 rounding ties them and the
    tie goes to expert 0."""
    layer = RoutedExperts(num_experts=4, experts_per_token=1, expert_width=8,
                          experts_held=1, expert_offset=1,
                          dtype=jnp.bfloat16)
    x = jnp.array([[[1.0, 1.0 + 2.0**-10, 0.0, 0.0]]], jnp.float32)
    params = layer.init(jax.random.key(0), x)["params"]
    params["router"] = jnp.eye(4, dtype=jnp.float32)

    def held(x):
        _, counts = layer.apply({"params": params}, x, mutable=["counters"])
        return int(counts["counters"]["rows_held"][0])

    assert held(x) == 1
    assert held(x.astype(jnp.bfloat16)) == 0


def test_a_lower_precision_fails_the_tolerance(reference, monkeypatch):
    """The norms' statistics, which the program keeps in float32, from a
    bf16 mean square in the reference instead: over the float32 bar."""
    model, params = _seeded()
    images = _images()
    got = jax.jit(lambda p: model.apply({"params": p}, images))(params)
    exact = reference._rms_norm

    def rounded(x, scale, eps):
        return exact(x.astype(jnp.bfloat16).astype(jnp.float32), scale, eps)

    monkeypatch.setattr(reference, "_rms_norm", rounded)
    want = jax.jit(lambda p: reference.forward(p, images, **KWARGS))(params)
    assert _rel(got, want) > 10 * VALUE_TOL


# the names one trace of the model's gradient gives ``saved_residual``
# (``remat.saved_residuals``): a layer's q, k, v and gate products, its
# gated heads' output and its attention output (6 x 6), the dense layers'
# up, gate and output (2 x 3), the expert layers' experts and shared
# expert, up and gate (4 x 4); and on the fused kernels each layer's
# kernel output and log-sum-exp (6 x 2)
SAVED_A_TRACE = {"xla": 58, "flash": 70}


def test_remat_changes_neither_the_tree_nor_the_numbers():
    """``remat`` under StreamMoEFormer's policy (the layers keep their
    products' and the attention kernel's outputs), on the XLA core and on
    the fused kernels: the same tree, and the output and every leaf's
    gradient as without it; the kept values are named once a trace."""
    images = _images()
    for backend, saved in SAVED_A_TRACE.items():
        plain, params = _seeded(attn_backend=backend)
        again, params_again = _seeded(remat=True, attn_backend=backend)
        assert jax.tree_util.tree_structure(params) == (
            jax.tree_util.tree_structure(params_again)
        )
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(params_again)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        values = []
        for m in (plain, again):
            before = metrics.report()["counters"].get(
                "remat.saved_residuals", 0
            )
            values.append(_out_and_grad(
                lambda p, m=m: m.apply({"params": p}, images)
            )(params))
            assert metrics.report()["counters"][
                "remat.saved_residuals"
            ] == before + saved, backend
        np.testing.assert_allclose(values[0][0], values[1][0], rtol=1e-5,
                                   atol=1e-6)
        for a, b in zip(*(jax.tree_util.tree_leaves(v[1]) for v in values)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)


def test_a_sliding_layer_is_counted_where_the_window_is_shorter():
    before = metrics.report()["counters"].get("attn.path.window", 0)
    model, params = _seeded()
    jax.jit(lambda p: model.apply({"params": p}, _images()))(params)
    after = metrics.report()["counters"]["attn.path.window"]
    # init and the jit each trace the 5 sliding layers of SSSFSS
    assert after - before == 10
    # a window as long as the frame is causal attention: nothing counted
    model, params = _seeded(window=42)
    jax.jit(lambda p: model.apply({"params": p}, _images()))(params)
    assert metrics.report()["counters"]["attn.path.window"] == after


def test_the_layers_have_the_published_parts():
    """Four norms a layer; q, k, v, their norms, the gate and proj; the
    first two layers dense, the rest experts with a gate and a shared
    expert; no position table."""
    _, params = _seeded()
    assert set(params) == {
        "patch_embed", "norm_f", "head", *(f"layer{i}" for i in range(6))
    }
    for i in range(6):
        layer = params[f"layer{i}"]
        assert set(layer) == {"attn", "ffn", "attn_norm", "attn_post_norm",
                              "ffn_norm", "ffn_post_norm"}
        assert {k: np.shape(v.get("kernel", v.get("scale")))
                for k, v in layer["attn"].items()} == {
            "q": (32, 4, 32), "k": (32, 2, 32), "v": (32, 2, 32),
            "q_norm": (32,), "k_norm": (32,), "gate": (32, 128),
            "proj": (128, 32),
        }
        assert set(layer["ffn"]) == ({"gate", "up", "down"} if i < 2 else {
            "router", "e_score_correction_bias", "experts_up",
            "experts_down", "experts_gate", "shared_up", "shared_gate",
            "shared_down",
        })


def test_published_widths_count_the_configurations_parameters():
    model = StreamMoEFormer(
        patch=8, dim=2048, layer_types="SSSFSS", num_dense_layers=2,
        num_heads=32, num_kv_heads=4, head_dim=128, window=2048,
        dense_width=6144, num_experts=128, experts_per_token=8,
        expert_width=1024, shared_width=1024, experts_held=8,
    )
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((2, 480, 640, 4), jnp.uint8))
    )["params"]
    assert sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    ) == 467_228_688


# -- the attention layer's parts against straight jnp -------------------------


def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        scale
    )


def _rotate(x, theta):
    t, d = x.shape[1], x.shape[-1]
    pos = np.arange(t)[:, None] / theta ** (np.arange(d // 2) * 2 / d)
    cos, sin = np.cos(pos)[None, :, None], np.sin(pos)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_grouped_attention_parts_are_straight_jnp(window, backend):
    """q/k RMSNorm over a head, RoPE on the normed q and k (pairs ``(i,
    i + D/2)`` turned by ``position / theta^(2i/D)``), a window on the
    causal mask, ``sigmoid(x W_gate)`` on the heads' output before
    ``proj``: each written out here in ``jnp``."""
    x = jax.random.normal(jax.random.key(8), (2, 16, 48))
    h, kv, d = 4, 2, 32
    module = MultiHeadAttention(
        h, num_kv_heads=kv, head_dim=d, use_bias=False, causal=True,
        qk_norm=True, rope_theta=500.0, output_gate=True, window=window,
        attn_backend=backend, dtype=jnp.float32,
    )
    params = module.init(jax.random.key(9), x)["params"]
    params["q_norm"]["scale"] = 1 + 0.1 * jnp.arange(d, dtype=jnp.float32)
    q, k, v = (jnp.einsum("btc,chd->bthd", x, params[n]["kernel"])
               for n in "qkv")
    q = _rotate(_rms(q, params["q_norm"]["scale"]), 500.0)
    k = _rotate(_rms(k, params["k_norm"]["scale"]), 500.0)
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    rows, cols = np.arange(16)[:, None], np.arange(16)[None, :]
    keep = (cols <= rows) & ((cols > rows - window) if window else True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)
    o = o.reshape(2, 16, h * d) * jax.nn.sigmoid(x @ params["gate"]["kernel"])
    want = o @ params["proj"]["kernel"]
    got = module.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_new_parts_are_off_by_default():
    """Without them ``_grouped`` is the parent's layer: no new parameter,
    the core without a window."""
    x = jax.random.normal(jax.random.key(8), (2, 16, 48))
    module = MultiHeadAttention(4, num_kv_heads=2, head_dim=32,
                                use_bias=False, causal=True,
                                dtype=jnp.float32)
    params = module.init(jax.random.key(9), x)["params"]
    assert set(params) == {"q", "k", "v", "proj"}
    q, k, v = (jnp.einsum("btc,chd->bthd", x, params[n]["kernel"])
               for n in "qkv")
    k, v = (jnp.repeat(a, 2, axis=2) for a in (k, v))
    want = reference_attention(q, k, v, causal=True).reshape(
        2, 16, 128) @ params["proj"]["kernel"]
    np.testing.assert_allclose(
        np.asarray(module.apply({"params": params}, x)), np.asarray(want),
        rtol=2e-5, atol=2e-5,
    )


# -- gated experts: the shares add up -------------------------------------------

LAYER = dict(num_experts=32, experts_per_token=8, expert_width=12,
             shared_width=20, scaling=2.826, gated=True, dtype=jnp.float32)


def _whole_layer(reference, params, x, held, offset):
    return reference._experts(
        params, x, per_token=LAYER["experts_per_token"],
        scaling=LAYER["scaling"], held=held, offset=offset,
    )


def test_gated_shares_add_up_to_the_whole_layer(reference):
    """16 chips hold 2 of 32 gated experts each (the deployment's split):
    the routed parts they compute, with the shared expert (which every
    chip computes alike) counted once, are what the uncut reference gives
    for the whole layer; each share is its reference given the share."""
    chips, held = 16, 2
    x = jax.random.normal(jax.random.key(3), (2, 11, 16))
    whole = RoutedExperts(**LAYER)
    params = whole.init(jax.random.key(4), x)["params"]
    params["e_score_correction_bias"] = 0.05 * jax.random.normal(
        jax.random.key(5), (32,)
    )
    shared = reference._swiglu(
        x, params["shared_gate"]["kernel"], params["shared_up"]["kernel"],
        params["shared_down"]["kernel"],
    )
    routed = 0.0
    for chip in range(chips):
        cut = slice(held * chip, held * (chip + 1))
        mine = dict(params, **{
            name: params[name][cut]
            for name in ("experts_gate", "experts_up", "experts_down")
        })
        part = RoutedExperts(
            **LAYER, experts_held=held, expert_offset=held * chip
        ).apply({"params": mine}, x)
        assert _rel(
            part, _whole_layer(reference, mine, x, held, held * chip)
        ) < 1e-5
        routed = routed + (part - shared)
    want = _whole_layer(reference, params, x, 32, 0)
    assert _rel(routed + shared, want) < 1e-5
    assert _rel(whole.apply({"params": params}, x), want) < 1e-5


def test_ungated_experts_are_unchanged():
    """``gated`` off (the default) adds no parameter: the layer
    ``nemotron3_nano_30b_a3b`` runs keeps its tree."""
    x = jax.random.normal(jax.random.key(3), (2, 11, 16))
    layer = RoutedExperts(**{**LAYER, "gated": False})
    assert set(layer.init(jax.random.key(4), x)["params"]) == {
        "router", "e_score_correction_bias", "experts_up", "experts_down",
        "shared_up", "shared_down",
    }
