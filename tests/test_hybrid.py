"""StreamHybrid is its plain reference, and what it reuses is unchanged.

``blendjax.models.StreamHybrid`` (Mamba-2 mixers through the chunked
scan, routed experts as one chip's share, grouped-query attention) at a
small size against ``benchmark/references/StreamHybrid.py`` (the layers'
equations in plain float32 ``jax.numpy``, which imports nothing of the
program) on seeded weights: the output and every leaf's gradient, pattern
``MEMEM*EME``. The share of the experts is tied to the whole layer; no
pick is dropped however the routing falls; the selection bias's gradient
is exactly zero; ``remat`` changes neither the tree nor the numbers; and
``MultiHeadAttention``'s defaults are the parent commit's bit for bit.
"""

import collections
import hashlib
import importlib.util
import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blendjax.models import RoutedExperts, StreamFormer, StreamHybrid
from blendjax.models.transformer import MultiHeadAttention
from blendjax.parallel.ring import reference_attention
from blendjax.utils.metrics import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN = "MEMEM*EME"
KWARGS = dict(
    patch=8, dim=32, pattern=PATTERN, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=8, n_groups=2, conv_kernel=4, chunk_size=16, num_heads=4,
    num_kv_heads=2, head_dim=16, num_experts=16, experts_per_token=3,
    expert_width=24, shared_width=48, routed_scaling=2.5, experts_held=4,
    expert_offset=4, norm_eps=1e-5, num_outputs=16, attn_backend="xla",
)
# float32 against float32: the same sums in another order (a chunked
# scan, a gate over every held expert, a fused projection) through 9
# residual layers. The output of O(1) reads 2e-7 of its largest entry, so
# 2e-6; a leaf's gradient sums over tokens and layers as well and reads
# up to 7e-6 of its largest entry, so 5e-5. One bf16 rounding of the
# router's logits, of the scan's decay exponent or of a norm's mean square
# moves the output by 3e-5 to 2e-2, fifteen times the output's bar at the
# least (test_a_lower_precision_fails_the_tolerance).
VALUE_TOL, GRAD_TOL = 2e-6, 5e-5
# bf16 compute against the float32 reference: activations and products
# carry 8 bits (2^-9 a rounding) through 9 layers, and a rounded router
# input flips a 3rd pick here and there; the output of O(1) moves by
# 1e-2 to 3e-2 (measured), so 8e-2. Gradients are not compared in bf16:
# a flipped pick moves an expert's whole gradient.
BF16_TOL = 8e-2


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "reference_stream_hybrid",
        os.path.join(ROOT, "benchmark", "references", "StreamHybrid.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _images(batch=2, h=48, w=56, seed=0):
    return jax.random.randint(
        jax.random.key(seed), (batch, h, w, 4), 0, 255
    ).astype(jnp.uint8)


SHORT = "ME*"  # one layer of each kind, where the whole stack is not the point


def _seeded(dtype=jnp.float32, **over):
    kwargs = {**KWARGS, **over}
    model = StreamHybrid(**kwargs, dtype=dtype)
    params = model.init(jax.random.key(1), _images())["params"]
    # a selection bias that matters: the published init is zeros
    for i, kind in enumerate(kwargs["pattern"]):
        if kind == "E":
            params[f"layer{i}"]["mixer"]["e_score_correction_bias"] = (
                0.05 * jax.random.normal(jax.random.key(100 + i), (16,))
            )
    return model, params


def _rel(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _out_and_grad(forward):
    """``params -> (output, gradient of a fixed weighting of it)``, one
    compiled program."""
    weights = jnp.cos(jnp.arange(16.0))

    def loss(params):
        out = forward(params)
        return jnp.sum(out.astype(jnp.float32) * weights), out

    def both(params):
        (_, out), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return out, grads

    return jax.jit(both)


@pytest.mark.parametrize("pattern, n_leaves", [
    (PATTERN, 75),   # the benchmark's stack
    ("EM*E", 34),    # an expert layer first and last
])
def test_the_model_is_its_reference(reference, pattern, n_leaves):
    kwargs = {**KWARGS, "pattern": pattern}
    model, params = _seeded(pattern=pattern)
    images = _images()
    got, got_g = _out_and_grad(
        lambda p: model.apply({"params": p}, images)
    )(params)
    want, want_g = _out_and_grad(
        lambda p: reference.forward(p, images, **kwargs)
    )(params)
    assert got.shape == want.shape == (2, 16)
    assert _rel(got, want) < VALUE_TOL
    leaves = jax.tree_util.tree_leaves_with_path(got_g)
    assert len(leaves) == len(jax.tree_util.tree_leaves(want_g)) == n_leaves
    for (path, g), w in zip(leaves, jax.tree_util.tree_leaves(want_g)):
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
        else:
            assert np.abs(np.asarray(w)).max() > 0, name
            assert _rel(g, w) < GRAD_TOL, name


@pytest.mark.parametrize("pattern", ["MEM*E", "EM*E"])
def test_bf16_compute_stays_near_the_float32_reference(reference, pattern):
    kwargs = {**KWARGS, "pattern": pattern}
    model, params = _seeded(dtype=jnp.bfloat16, pattern=pattern)
    images = _images()
    got = jax.jit(lambda p: model.apply({"params": p}, images))(params)
    want = jax.jit(lambda p: reference.forward(p, images, **kwargs))(params)
    assert got.dtype == jnp.float32  # the head's
    assert _rel(got, want) < BF16_TOL


class _Rounding:
    """A module stand-in whose ``name`` rounds its argument to bf16 first."""

    def __init__(self, module, name):
        self._module, self._name = module, name

    def __getattr__(self, name):
        fn = getattr(self._module, name)
        if name != self._name:
            return fn
        return lambda x, *a, **k: fn(
            x.astype(jnp.bfloat16).astype(jnp.float32), *a, **k
        )


def _jax_with(**over):
    """What the reference reads of ``jax``, one module of it replaced."""
    return types.SimpleNamespace(**{
        "nn": jax.nn, "lax": jax.lax, "checkpoint": jax.checkpoint, **over,
    })


@pytest.mark.parametrize("what", ["router", "decay", "norm"])
def test_a_lower_precision_fails_the_tolerance(reference, monkeypatch, what):
    """What the program pins to float32, computed from a bf16 value in
    the reference instead, reads over the float32 bar: the comparison is
    tight enough to see one such rounding."""
    kwargs = KWARGS  # the whole stack: a rounding compounds over its layers
    model, params = _seeded()
    images = _images()
    got = jax.jit(lambda p: model.apply({"params": p}, images))(params)
    if what == "router":  # the scores from bf16 logits
        monkeypatch.setattr(
            reference, "jax", _jax_with(nn=_Rounding(jax.nn, "sigmoid"))
        )
    elif what == "decay":  # exp of a bf16 sum of dt * A
        monkeypatch.setattr(reference, "jnp", _Rounding(jnp, "exp"))
    else:  # a norm's rsqrt from a bf16 mean square
        monkeypatch.setattr(
            reference, "jax", _jax_with(lax=_Rounding(jax.lax, "rsqrt"))
        )
    want = jax.jit(lambda p: reference.forward(p, images, **kwargs))(params)
    assert _rel(got, want) > 10 * VALUE_TOL


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pattern", [SHORT, PATTERN])
def test_remat_changes_neither_the_tree_nor_the_losses(pattern, dtype):
    """``remat`` keeps the large products' outputs and recomputes the
    rest of a layer: the same tree, and the output and every parameter's
    gradient as without it (the values saved are the values recomputed).

    Recomputing a layer may fuse and so round its sums otherwise: in
    float32 a few ulps; in bf16 XLA rounds the recomputed layer's
    intermediates in other places, and a gradient moves by up to 1.5
    times what bf16 compute moves it from the float32 gradient (measured,
    parent and change alike; the output does not move), so 3 times."""
    images = _images()
    plain, params = _seeded(dtype=dtype, pattern=pattern)
    again, params_again = _seeded(dtype=dtype, pattern=pattern, remat=True)
    assert jax.tree_util.tree_structure(params) == (
        jax.tree_util.tree_structure(params_again)
    )
    assert all(f"layer{i}" in params for i in range(len(pattern)))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params_again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    values = [
        _out_and_grad(lambda p, m=m: m.apply({"params": p}, images))(params)
        for m in (plain, again)
    ]
    np.testing.assert_allclose(values[0][0], values[1][0], rtol=1e-5,
                               atol=1e-6)
    leaves = [jax.tree_util.tree_leaves_with_path(v[1]) for v in values]
    assert len(leaves[0]) == len(leaves[1]) == (75 if pattern == PATTERN
                                                else 27)
    if dtype == jnp.bfloat16:
        f32 = _seeded(pattern=pattern)[0]
        want = jax.tree_util.tree_leaves(_out_and_grad(
            lambda p: f32.apply({"params": p}, images)
        )(params)[1])
    for i, ((path, a), (_, b)) in enumerate(zip(*leaves)):
        name = jax.tree_util.keystr(path)
        if dtype == jnp.float32:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6, err_msg=name)
        elif not np.asarray(a).any():  # the selection bias's
            assert not np.asarray(b).any(), name
        else:
            assert _rel(b, a) <= 3 * _rel(a, want[i]), name


def _dot_general_results(model, images, params):
    """The result type of every ``dot_general`` in the lowered program
    of ``value_and_grad`` over ``model``'s output, counted."""
    text = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(model.apply({"params": p}, images))
    )).lower(params).as_text()
    return collections.Counter(
        re.findall(r"stablehlo.dot_general .*-> (tensor<[^>]*>)", text)
    )


def test_remat_recomputes_none_of_the_large_products():
    """Under ``remat`` the backward recomputes a layer's forward but for
    the outputs ``saved_residual`` names: the held experts' up-product,
    the shared expert's hidden pre-activation and the Mamba-2 input
    projection are made as often as without ``remat`` (once forward;
    the down-products' input gradients share the up-products' shapes),
    where plain ``remat`` made each once more. The other products, the
    router's and the attention's, are still recomputed."""
    images = _images()
    kw = KWARGS
    n = images.shape[0] * (images.shape[1] // kw["patch"]) * (
        images.shape[2] // kw["patch"]
    )
    inner = kw["mamba_num_heads"] * kw["mamba_head_dim"]
    in_proj = 2 * inner + 2 * kw["n_groups"] * kw["ssm_state_size"] + (
        kw["mamba_num_heads"]
    )
    tagged = (
        f"tensor<{n}x{kw['experts_held']}x{kw['expert_width']}xf32>",
        f"tensor<{n}x{kw['shared_width']}xbf16>",
        f"tensor<{images.shape[0]}x{n // images.shape[0]}x{in_proj}xbf16>",
    )
    plain, params = _seeded(dtype=jnp.bfloat16)
    again, _ = _seeded(dtype=jnp.bfloat16, remat=True)
    without = _dot_general_results(plain, images, params)
    with_remat = _dot_general_results(again, images, params)
    for shape in tagged:
        assert without[shape] > 0, (shape, sorted(without))
        assert with_remat[shape] == without[shape], shape
    assert sum(with_remat.values()) > sum(without.values())


def test_the_saved_residuals_are_counted_once_a_trace():
    images = _images()
    model, params = _seeded(pattern="MME*", remat=True)
    before = metrics.report()["counters"].get("remat.saved_residuals", 0)
    jax.jit(lambda p: model.apply({"params": p}, images))(params)
    after = metrics.report()["counters"].get("remat.saved_residuals", 0)
    # two Mamba-2 input projections, the expert layer's two products, the
    # attention layer's q, k, v and output, which the policy does not keep
    # (the XLA scan and attention on the CPU name nothing)
    assert after - before == 8


def test_the_paths_are_counted_once_a_trace():
    images = _images()
    before = dict(metrics.report()["counters"])
    model, params = _seeded(pattern="MME*")
    jax.jit(lambda p: model.apply({"params": p}, images))(params)
    after = metrics.report()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in (
        "ssm.path.chunked", "attn.path.gqa",
    )}
    # init traces the model once more than the jit does
    assert moved == {"ssm.path.chunked": 4, "attn.path.gqa": 2}


# -- the expert layer: shares, drops -----------------------------------------------

LAYER = dict(num_experts=32, experts_per_token=6, expert_width=12,
             shared_width=20, scaling=2.5, dtype=jnp.float32)


def _whole_layer(reference, params, x, held, offset):
    return reference._experts(
        params, x, per_token=LAYER["experts_per_token"],
        scaling=LAYER["scaling"], held=held, offset=offset,
    )


@pytest.mark.parametrize("chips", [16, 8])
def test_the_shares_add_up_to_the_whole_layer(reference, chips):
    """16 chips hold 2 of 32 experts each (the deployment's split), or 8
    hold 4: the routed parts they compute, with the shared expert (which
    every chip computes alike) counted once, are what the uncut
    reference gives for the whole layer."""
    held = 32 // chips
    x = jax.random.normal(jax.random.key(3), (2, 11, 16))
    whole = RoutedExperts(**LAYER)
    params = whole.init(jax.random.key(4), x)["params"]
    params["e_score_correction_bias"] = 0.05 * jax.random.normal(
        jax.random.key(5), (32,)
    )
    shared = reference._relu2(
        x @ params["shared_up"]["kernel"]
    ) @ params["shared_down"]["kernel"]
    routed = 0.0
    for chip in range(chips):
        mine = dict(
            params,
            experts_up=params["experts_up"][held * chip:held * (chip + 1)],
            experts_down=params["experts_down"][held * chip:held * (chip + 1)],
        )
        part = RoutedExperts(
            **LAYER, experts_held=held, expert_offset=held * chip
        ).apply({"params": mine}, x)
        # a share is its reference, given the same share
        assert _rel(
            part, _whole_layer(reference, mine, x, held, held * chip)
        ) < 1e-5
        routed = routed + (part - shared)
    want = _whole_layer(reference, params, x, 32, 0)
    assert _rel(routed + shared, want) < 1e-5
    assert _rel(whole.apply({"params": params}, x), want) < 1e-5


@pytest.mark.parametrize("favoured", [
    [4, 5, 6, 7, 8, 9],        # every pick of every token lands here
    [4, 0, 1, 2, 3, 20],       # one held expert gets a pick of every token
    [0, 1, 2, 3, 20, 21],      # nothing lands here
    [3, 9, 10, 11, 12, 13],    # the last held expert, its neighbours absent
    [2, 3, 10, 11, 30, 31],    # nothing: the experts just outside the share
])
def test_no_pick_is_dropped_however_the_routing_falls(reference, favoured):
    """A selection bias that sends every token to the same six experts:
    the held ones (4 to 9 of 32) get all of a token's picks, one, or none,
    and the layer is still its reference, which has no capacity."""
    x = jax.random.normal(jax.random.key(6), (2, 9, 16))
    layer = RoutedExperts(**LAYER, experts_held=6, expert_offset=4)
    params = layer.init(jax.random.key(7), x)["params"]
    params["e_score_correction_bias"] = jnp.zeros(32).at[
        jnp.asarray(favoured)
    ].set(10.0)
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(layer.apply({"params": p}, x) ** 2)
    ))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(_whole_layer(reference, p, x, 6, 4) ** 2)
    ))(params)
    assert _rel(got, want) < 1e-5
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < 1e-4 * (
            1 + np.abs(np.asarray(w)).max()
        )
    assert not np.asarray(grads["e_score_correction_bias"]).any()


# -- attention: fewer key/value heads; the defaults as they were ---------------------


@pytest.mark.parametrize("backend", ["xla", "flash"])
@pytest.mark.parametrize("h, kv, d", [(4, 2, 128), (8, 1, 128), (2, 2, 128)])
def test_grouped_query_attention_is_attention_over_repeated_heads(
    h, kv, d, backend
):
    x = jax.random.normal(jax.random.key(8), (2, 16, 48))
    module = MultiHeadAttention(
        h, num_kv_heads=kv, head_dim=d, use_bias=False, causal=True,
        attn_backend=backend, dtype=jnp.float32,
    )
    params = module.init(jax.random.key(9), x)["params"]
    assert {k: v["kernel"].shape for k, v in params.items()} == {
        "q": (48, h, d), "k": (48, kv, d), "v": (48, kv, d),
        "proj": (h * d, 48),
    }
    q, k, v = (jnp.einsum("btc,chd->bthd", x, params[n]["kernel"])
               for n in "qkv")
    k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
    want = reference_attention(q, k, v, causal=True).reshape(
        2, 16, h * d
    ) @ params["proj"]["kernel"]
    got = module.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in sorted(
        jax.tree_util.tree_leaves_with_path(tree),
        key=lambda kv: jax.tree_util.keystr(kv[0]),
    ):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype, answer", [
    (jnp.float32,
     "2bb491214f2b9b91231fb415ae862928a9f9844ce430a670cd81af954d461bbd"),
    (jnp.bfloat16,
     "a0257caf9a30d2cfdc0b4e18d98e19f657649669d2eda0bca5f1ce3c667b1263"),
])
def test_streamformer_is_the_parent_commits_bit_for_bit(dtype, answer):
    """``MultiHeadAttention`` gained ``num_kv_heads``, ``head_dim`` and
    ``use_bias``; with their defaults a seeded ``StreamFormer`` has the
    parameters and gives the answer the parent commit (13c0256) gave:
    digests recorded there."""
    images = (np.arange(2 * 32 * 48 * 4) % 251).astype(np.uint8).reshape(
        2, 32, 48, 4
    )
    model = StreamFormer(patch=8, dim=32, depth=2, num_heads=4, dtype=dtype)
    params = model.init(jax.random.key(7), images)["params"]
    assert _digest(params) == (
        "d8b28c8dd2cecce0cc7c49fb3ecc4608c7733b21245e41edc6ce56bd5d99aa3d"
    )
    assert _digest({"out": model.apply({"params": params}, images)}) == answer


@pytest.mark.parametrize("name, answer", [
    ("streamformer_conv_embed_snapshot",
     "778ee533421b68a33a03a57be4c1e61fb4d7c5de0ddabf0ee380c023ed7ed617"),
    ("streamformer_dense_qkv_snapshot",
     "2f16c2b881441790d40803477cc28b175441401d3b10c916fd73988ea3e40278"),
])
def test_the_saved_snapshots_are_untouched(name, answer):
    """The two states earlier trees saved (tests/test_patch_embed.py and
    tests/test_attention.py restore them into today's model) are the
    files they were, to the last bit."""
    root = os.path.join(ROOT, "tests", "fixtures", name)
    h = hashlib.sha256()
    for base, dirs, files in sorted(os.walk(root)):
        dirs.sort()
        for f in sorted(files):
            path = os.path.join(base, f)
            h.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as fh:
                h.update(fh.read())
    assert h.hexdigest() == answer
