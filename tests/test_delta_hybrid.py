"""StreamDeltaHybrid is its plain reference, and its shares are the layer.

``blendjax.models.StreamDeltaHybrid`` (Olmo's reordered-norm layers:
gated delta-rule linear attention ``L`` beside full attention ``F`` with
q/k norms over the whole projection, a SwiGLU each, as one chip's share
of the heads) at a small size against
``benchmark/references/StreamDeltaHybrid.py`` (the layers' equations in
plain float32 ``jax.numpy``, the recurrence token by token, which imports
nothing of the program) on seeded weights: the output and every leaf's
gradient, on the XLA core and on the fused kernels (interpreter mode).
The two halves of the heads add up to the uncut layer, ``remat`` changes
neither tree nor numbers, and ``layer_types`` takes L and F alone.
"""

import importlib.util
import os
import re

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blendjax.models import GatedDeltaNetMixer, StreamDeltaHybrid
from blendjax.models.transformer import MultiHeadAttention
from blendjax.utils.metrics import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 42 tokens a frame (48 x 56 at patch 8): chunks of 16 leave a ragged
# last one; 4 of 8 heads held from head 2; the full layer's 4 heads of 32
# fill the fused kernels' 128 lanes
KWARGS = dict(
    patch=8, dim=64, layer_types="LLLF", num_heads=8, heads_held=4,
    head_offset=2, head_dim=32, key_dim=16, value_dim=32, conv_kernel=4,
    chunk=16, mlp_width=96, norm_eps=1e-6, num_outputs=16,
)
# float32 against float32, the recurrence chunked against token by token
# through 4 post-normed layers. Measured: the output 1.6e-6 of its
# largest entry, a leaf's gradient up to 4.4e-5 of its largest (over 3
# seeds at 2 heads held: 1.5e-6 and 3.7e-5). The gradients are the worse conditioned: each post-norm
# divides by its mixer's small output, and the decays' and q's
# gradients sum over tokens with cancellation; against the float64
# composition the reference's own float32 gradients are 3e-4 off at half
# this width, as far as the program's. So 5e-6 and 2e-4 (about 5 times
# the readings); a bf16 rounding of the recurrence's decays reads over
# ten times the output's bar (test_a_lower_precision_fails_the_tolerance).
VALUE_TOL, GRAD_TOL = 5e-6, 2e-4
# bf16 compute against the float32 reference through 4 layers: the
# output of O(1) moves by a few 1e-2 (measured), so hybrid's 8e-2
BF16_TOL = 8e-2


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "reference_stream_delta_hybrid",
        os.path.join(ROOT, "benchmark", "references", "StreamDeltaHybrid.py"),
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
    model = StreamDeltaHybrid(**kwargs, dtype=dtype)
    return model, model.init(jax.random.key(1), _images())["params"]


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
    # 5 (patch embedding, final norm, head) + 3 x (13 mixer + 3 SwiGLU +
    # 2 norms) + (4 attention + 2 q/k norms + 3 SwiGLU + 2 norms)
    assert len(leaves) == len(jax.tree_util.tree_leaves(want_g)) == 70
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want_g)):
        name = jax.tree_util.keystr(path)
        assert np.abs(np.asarray(w)).max() > 0, name
        assert _rel(g, w) < GRAD_TOL, name


def test_bf16_compute_stays_near_the_float32_reference(reference):
    model, params = _seeded(dtype=jnp.bfloat16)
    images = _images()
    got = jax.jit(lambda p: model.apply({"params": p}, images))(params)
    want = jax.jit(lambda p: reference.forward(p, images, **KWARGS))(params)
    assert got.dtype == jnp.float32  # the head's
    assert _rel(got, want) < BF16_TOL


def test_a_lower_precision_fails_the_tolerance(reference, monkeypatch):
    """The recurrence's decays rounded to bf16 in the reference read over
    ten times the output's bar: the comparison sees a change that
    small."""
    model, params = _seeded()
    images = _images()
    got = jax.jit(lambda p: model.apply({"params": p}, images))(params)
    rule = reference._delta_rule

    def rounded(q, k, v, g, beta):
        return rule(q, k, v, g.astype(jnp.bfloat16).astype(jnp.float32), beta)

    monkeypatch.setattr(reference, "_delta_rule", rounded)
    want = jax.jit(lambda p: reference.forward(p, images, **KWARGS))(params)
    assert _rel(got, want) > 10 * VALUE_TOL


def test_the_residual_stream_is_float32_under_bf16_compute():
    """The embedding and every layer's output are float32; the mixers'
    and the SwiGLUs' products are bf16."""
    model, params = _seeded(dtype=jnp.bfloat16)
    _, state = model.apply(
        {"params": params}, _images(), capture_intermediates=True,
    )
    found = state["intermediates"]
    assert found["patch_embed"]["__call__"][0].dtype == jnp.float32
    for i in range(4):
        layer = found[f"layer{i}"]
        assert layer["__call__"][0].dtype == jnp.float32, i
        assert layer["ffn"]["__call__"][0].dtype == jnp.bfloat16, i
        assert layer["attn"]["__call__"][0].dtype == jnp.bfloat16, i


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_remat_changes_neither_the_tree_nor_the_numbers(dtype):
    images = _images()
    values = []
    for remat in (False, True):
        model, params = _seeded(dtype=dtype, remat=remat)
        values.append(_out_and_grad(
            lambda p, model=model: model.apply({"params": p}, images)
        )(params))
    (out0, g0), (out1, g1) = values
    assert (jax.tree_util.tree_structure(g0)
            == jax.tree_util.tree_structure(g1))
    # the same sums, fused otherwise around the recomputed layers
    assert _rel(out1, out0) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert _rel(b, a) < 1e-4


def test_remat_runs_the_recurrence_kernel_once_a_layer(monkeypatch):
    """With the kernel pair taken (forced here; ``auto`` takes it on a
    TPU), the ``remat`` policy keeps each ``L`` layer's recurrence output
    and chunk states: the lowered step calls the forward kernel once a
    layer and the backward once, and the output and every leaf's gradient
    are the XLA form's."""
    import blendjax.ops.delta_rule as rule

    images = _images()
    model, params = _seeded(remat=True)

    def forward(p):
        return model.apply({"params": p}, images)

    want, want_g = _out_and_grad(forward)(params)
    monkeypatch.setattr(rule, "auto_picks_kernel", lambda *a, **kw: True)
    step = _out_and_grad(forward)
    text = step.lower(params).as_text()
    assert len(re.findall(r"call @_rule_fwd(_\d+)?\(", text)) == 3
    assert len(re.findall(r"call @_rule_bwd(_\d+)?\(", text)) == 3
    got, got_g = step(params)
    assert _rel(got, want) < VALUE_TOL
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert _rel(g, w) < GRAD_TOL


@pytest.mark.parametrize("over, message", [
    (dict(layer_types="LLSF"), "letters are L and F"),
    (dict(layer_types=""), "letters are L and F"),
    (dict(heads_held=3, head_offset=6), "heads 6 to 9 of 8"),
    (dict(head_offset=-1), "of 8"),
])
def test_layer_types_and_the_share_are_checked(over, message):
    model = StreamDeltaHybrid(**{**KWARGS, **over})
    with pytest.raises(ValueError, match=message):
        model.init(jax.random.key(0), _images())


def test_the_paths_are_counted_once_a_trace():
    model, params = _seeded()
    before = metrics.report()["counters"]
    jax.jit(lambda p: model.apply({"params": p}, _images())).lower(params)
    after = metrics.report()["counters"]
    assert after["gdn.path.chunked"] - before.get("gdn.path.chunked", 0) == 3


def _halves(module_cls, whole_kwargs, heads):
    """The uncut module and its two halves by heads, each initialised
    from one key."""
    x = jax.random.normal(jax.random.key(7), (2, 21, 64))
    whole = module_cls(**whole_kwargs)
    params = whole.init(jax.random.key(3), x)["params"]
    halves = []
    for offset in (0, heads // 2):
        half = module_cls(**{**whole_kwargs, "heads_held": heads // 2,
                             "head_offset": offset})
        halves.append((half, half.init(jax.random.key(3), x)["params"],
                       offset))
    return x, whole, params, halves


def test_linear_attention_halves_add_up_to_the_whole_layer():
    """Heads 0-1 and 2-3 of a gated delta-rule mixer, each drawn from the
    uncut layer's key: their parameters are the uncut layer's rows and
    columns, and their outputs (before the post-norm) sum to its output,
    every head's part computed alike."""
    kw = dict(num_heads=4, key_dim=16, value_dim=32, chunk=8,
              dtype=jnp.float32)
    x, whole, params, halves = _halves(GatedDeltaNetMixer, kw, 4)
    for half, mine, offset in halves:
        cols = slice(offset * 16, (offset + 2) * 16)
        np.testing.assert_array_equal(
            mine["q_proj"]["kernel"], params["q_proj"]["kernel"][:, cols]
        )
        np.testing.assert_array_equal(
            mine["o_proj"]["kernel"],
            params["o_proj"]["kernel"][offset * 32:(offset + 2) * 32],
        )
        np.testing.assert_array_equal(
            mine["A_log"], params["A_log"][offset:offset + 2]
        )
        np.testing.assert_array_equal(
            mine["v_conv"], params["v_conv"][:, offset * 32:(offset + 2) * 32]
        )
    want = whole.apply({"params": params}, x)
    parts = sum(half.apply({"params": mine}, x)
                for half, mine, _ in halves)
    assert _rel(parts, want) < 1e-6


def test_full_attention_halves_add_up_given_the_same_normed_q_k():
    """Heads 0-1 and 2-3 of a full layer sum to the uncut layer once they
    are given its q and k after the whole-projection norm: the one part
    of a layer whose statistics a deployment reduces across its chips
    (the configuration's departures)."""
    kw = dict(num_heads=4, head_dim=16, use_bias=False, causal=True,
              qk_norm="projection", heads_held=4, attn_backend="xla",
              dtype=jnp.float32)
    x, whole, params, halves = _halves(MultiHeadAttention, kw, 4)
    normed = {}

    def keep(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if context.module.name in ("q_norm", "k_norm"):
            normed[context.module.name] = out
        return out

    with nn.intercept_methods(keep):
        want = whole.apply({"params": params}, x)
    assert set(normed) == {"q_norm", "k_norm"}
    parts = 0.0
    for half, mine, offset in halves:
        np.testing.assert_array_equal(
            mine["q"]["kernel"], params["q"]["kernel"][:, offset:offset + 2]
        )
        np.testing.assert_array_equal(
            mine["proj"]["kernel"],
            params["proj"]["kernel"][offset * 16:(offset + 2) * 16],
        )

        def given(next_fun, args, kwargs, context, offset=offset):
            if context.module.name not in normed:
                return next_fun(*args, **kwargs)
            out = normed[context.module.name]
            b, t, _ = out.shape
            return out.reshape(b, t, 4, 16)[:, :, offset:offset + 2].reshape(
                b, t, 32
            )

        with nn.intercept_methods(given):
            parts = parts + half.apply({"params": mine}, x)
    assert _rel(parts, want) < 1e-6


def test_the_whole_projection_norm_is_straight_jnp():
    """``qk_norm="projection"``: q and k each RMS-normed over all their
    heads' channels with one scale, then attention as written."""
    b, t, c, h, d = 2, 13, 32, 4, 8
    x = jax.random.normal(jax.random.key(2), (b, t, c))
    layer = MultiHeadAttention(
        num_heads=h, head_dim=d, use_bias=False, causal=True,
        qk_norm="projection", norm_eps=1e-6, attn_backend="xla",
        dtype=jnp.float32,
    )
    params = layer.init(jax.random.key(4), x)["params"]
    params["q_norm"]["scale"] = 1.0 + 0.1 * jnp.arange(h * d)
    params["k_norm"]["scale"] = 1.0 - 0.02 * jnp.arange(h * d)

    def rms(y, scale):
        return y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6) * scale

    q, k, v = (x @ params[n]["kernel"].reshape(c, h * d) for n in "qkv")
    q, k = rms(q, params["q_norm"]["scale"]), rms(k, params["k_norm"]["scale"])
    q, k, v = (a.reshape(b, t, h, d) for a in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    want = o.reshape(b, t, h * d) @ params["proj"]["kernel"]
    got = layer.apply({"params": params}, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_configurations_parameters_at_published_widths():
    """``olmo_hybrid_7b``'s share at every published width: 670,952,362
    parameters (the configuration's count), from shapes alone."""
    model = StreamDeltaHybrid(
        patch=8, dim=3840, layer_types="LLLF", num_heads=30, heads_held=15,
        head_dim=128, key_dim=96, value_dim=192, mlp_width=11008,
    )
    shapes = jax.eval_shape(
        model.init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 480, 640, 4), jnp.uint8),
    )["params"]
    sizes = {
        k: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(v))
        for k, v in shapes.items()
    }
    assert sum(sizes.values()) == 670_952_362
    # a linear layer: 15 heads' q, k (96), v, gate (192), a, b, convs,
    # A_log, dt_bias, the shared norm, o_proj; SwiGLU; two norms
    mixer = (3840 * 15 * (2 * 96 + 2 * 192 + 2) + 4 * 15 * (2 * 96 + 192)
             + 2 * 15 + 192 + 15 * 192 * 3840)
    assert sizes["layer0"] == mixer + 3 * 3840 * 11008 + 2 * 3840
    assert sizes["layer3"] == (4 * 3840 * 1920 + 2 * 1920
                               + 3 * 3840 * 11008 + 2 * 3840)
