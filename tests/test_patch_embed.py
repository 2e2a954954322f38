"""The patch embedding is a matrix product over patches, and it is the
strided convolution it replaced.

``StreamFormer``'s ``patch_embed`` was an ``nn.Conv`` with a ``patch`` x
``patch`` window and stride; it is :class:`blendjax.models.transformer.
PatchEmbed` over :func:`blendjax.ops.image.embed_patches` now (PR 34:
the convolution read the u8 frames at 19 GB/s on the chip). Held here:
the same numbers as the convolution, value and both gradients, on u8 and
float frames, for the patch sizes, channel counts and grids the static
shape may bring; the same parameter tree from the same key; and a state
saved by the convolution's model restoring into this one.
"""

import os
import shutil

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from blendjax.checkpoint import SnapshotManager
from blendjax.models import StreamFormer
from blendjax.models.transformer import PatchEmbed
from blendjax.ops.image import embed_patches, maybe_normalize_uint8
from blendjax.train import make_train_state

FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "streamformer_conv_embed_snapshot",
)
DIM = 24
# f32: two summation orders of p*p*C products of O(1) x O(0.1) terms;
# bf16: one rounding of the result (2^-9 relative) on top, the policy's
# compute dtype with float32 accumulation on both sides
TOLERANCE = {jnp.float32: 1e-6, jnp.bfloat16: 1e-2}


class _ConvStem(nn.Module):
    """The stem as it was: what ``StreamFormer.__call__`` did before."""

    patch: int
    dtype: object

    @nn.compact
    def __call__(self, images):
        x = maybe_normalize_uint8(images, self.dtype)
        return nn.Conv(
            DIM, (self.patch, self.patch), strides=(self.patch, self.patch),
            dtype=self.dtype, param_dtype=jnp.float32, name="patch_embed",
        )(x)


class _ProductStem(nn.Module):
    patch: int
    dtype: object

    @nn.compact
    def __call__(self, images):
        return PatchEmbed(
            DIM, self.patch, dtype=self.dtype, name="patch_embed"
        )(images)


def _frames(patch, channels, grid, kind, seed=0):
    gh, gw = grid
    rng = np.random.default_rng(seed)
    shape = (2, gh * patch, gw * patch, channels)
    if kind == "u8":
        return rng.integers(0, 256, shape, np.uint8)
    return rng.random(shape, np.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["u8", "float"])
@pytest.mark.parametrize("grid", [(2, 3), (3, 4)], ids=["2x3", "3x4"])
@pytest.mark.parametrize("channels", [3, 4])
@pytest.mark.parametrize("patch", [8, 16])
def test_embedding_equals_the_convolution(patch, channels, grid, kind, dtype):
    """Value and the gradients of ``kernel`` and ``bias`` against
    ``nn.Conv`` on the same parameters: non-square grids of odd and even
    width, 3 and 4 channels, u8 frames (scaled to [0, 1]) and float ones."""
    images = _frames(patch, channels, grid, kind)
    conv, product = _ConvStem(patch, dtype), _ProductStem(patch, dtype)
    params = conv.init(jax.random.key(1), images)["params"]
    params["patch_embed"]["bias"] = 0.1 * jax.random.normal(
        jax.random.key(2), (DIM,), jnp.float32
    )
    weight = jax.random.normal(
        jax.random.key(3), (2, *grid, DIM), jnp.float32
    )

    def value_and_grads(stem):
        def loss(p):
            y = stem.apply({"params": p}, images)
            return jnp.sum(y.astype(jnp.float32) * weight), y

        (_, y), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return y, grads["patch_embed"]

    want, want_grads = value_and_grads(conv)
    got, got_grads = value_and_grads(product)
    assert got.shape == want.shape == (2, *grid, DIM)
    assert got.dtype == want.dtype == dtype
    tol = TOLERANCE[dtype]

    def close(a, b):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())

    close(got, want)
    for leaf in ("kernel", "bias"):
        assert got_grads[leaf].shape == want_grads[leaf].shape
        assert got_grads[leaf].dtype == jnp.float32
        close(got_grads[leaf], want_grads[leaf])


@pytest.mark.parametrize("patch, channels", [(8, 4), (16, 3)])
def test_same_parameters_from_the_same_key(patch, channels):
    """``patch_embed/kernel`` is ``(p, p, C, dim)`` float32 and
    ``patch_embed/bias`` ``(dim,)``, drawn as ``nn.Conv`` drew them: a
    seeded run starts from the same numbers (the benchmark's seeded
    checksum, ``benchmark/reference.py``, depends on it)."""
    images = _frames(patch, channels, (2, 3), "u8")
    old = _ConvStem(patch, jnp.bfloat16).init(jax.random.key(7), images)
    new = _ProductStem(patch, jnp.bfloat16).init(jax.random.key(7), images)
    assert jax.tree_util.tree_structure(old) == jax.tree_util.tree_structure(new)
    kernel = new["params"]["patch_embed"]["kernel"]
    assert kernel.shape == (patch, patch, channels, DIM)
    assert kernel.dtype == jnp.float32
    for a, b in zip(jax.tree_util.tree_leaves(old),
                    jax.tree_util.tree_leaves(new)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_helper_takes_float_kernel_of_any_patch_and_rejects_a_ragged_frame():
    """The helper alone: the reference's own arithmetic
    (``benchmark/references/StreamFormer.py``: reshape, transpose,
    ``x @ kernel.reshape(p*p*c, -1)``) in float32, and a frame that is
    not whole patches is an error, as it was for the model's reshape."""
    p, c = 4, 3
    images = _frames(p, c, (3, 5), "float", seed=4)
    kernel = jax.random.normal(jax.random.key(5), (p, p, c, DIM), jnp.float32)
    bias = jnp.arange(DIM, dtype=jnp.float32)
    x = images.reshape(2, 3, p, 5, p, c).transpose(0, 1, 3, 2, 4, 5)
    want = x.reshape(2, 3, 5, p * p * c) @ np.asarray(kernel).reshape(
        p * p * c, -1
    ) + np.asarray(bias)
    got = embed_patches(images, kernel, bias, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    with pytest.raises((ValueError, TypeError)):
        embed_patches(images[:, :-1], kernel, bias, jnp.float32)


def test_a_state_saved_by_the_convolution_restores_into_the_product(tmp_path):
    """``tests/fixtures/streamformer_conv_embed_snapshot`` was written by
    the tree before this change (PR 33's, ``nn.Conv`` stem: the state
    after one sgd update, the frames it saw and what it answered). It
    restores leaf for leaf into today's model, which answers the same."""
    directory = tmp_path / "snapshot"
    shutil.copytree(FIXTURE, directory)  # the manager sweeps what it opens
    model = StreamFormer(patch=8, dim=16, depth=1, num_heads=2,
                         num_outputs=16, dtype=jnp.float32,
                         attn_backend="xla")
    template = make_train_state(
        model, np.zeros((2, 16, 24, 4), np.uint8),
        optimizer=optax.sgd(1e-2), rng=jax.random.key(0),
    )
    mgr = SnapshotManager(str(directory), keep=1)
    try:
        restored = mgr.restore(template)
    finally:
        mgr.close()
    assert restored is not None and restored.step == 1
    kernel = restored.state.params["patch_embed"]["kernel"]
    assert kernel.shape == (8, 8, 4, 16) and kernel.dtype == jnp.float32
    assert float(jnp.abs(kernel).sum()) > 0
    out = model.apply(
        {"params": restored.state.params}, restored.session["images"]
    )
    np.testing.assert_allclose(
        np.asarray(out), restored.session["outputs"], rtol=1e-5, atol=1e-6
    )
