"""Models, train steps, checkpointing, and image ops on the CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from blendjax.models import (  # noqa: E402
    CubeRegressor,
    Discriminator,
    PolicyValueNet,
    StreamFormer,
)
from blendjax.ops import (  # noqa: E402
    gamma_correct,
    normalize_uint8,
    random_flip,
    uint8_gamma_normalize,
)
from blendjax.parallel import batch_sharding, create_mesh  # noqa: E402
from blendjax.train import (  # noqa: E402
    CheckpointManager,
    corner_loss,
    make_eval_step,
    make_supervised_step,
    make_train_state,
)


def _batch(b=8, h=64, w=64, rng=None):
    rng = rng or np.random.default_rng(0)
    return {
        "image": rng.integers(0, 255, (b, h, w, 4), dtype=np.uint8),
        "xy": rng.uniform(0, 64, (b, 8, 2)).astype(np.float32),
    }


def test_cube_regressor_trains_loss_decreases():
    mesh = create_mesh({"data": 8})
    sharding = batch_sharding(mesh)
    model = CubeRegressor(features=(8, 16))
    batch = {
        k: jax.device_put(v, sharding) for k, v in _batch().items()
    }
    state = make_train_state(
        model, jnp.zeros((8, 64, 64, 4), jnp.uint8), learning_rate=1e-2,
        mesh=mesh,
    )
    step = make_supervised_step(mesh=mesh, batch_sharding=sharding)
    state, m0 = step(state, batch)
    losses = [float(m0["loss"])]
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses
    assert int(state.step) == 11


def test_eval_step_metrics():
    model = CubeRegressor(features=(8,))
    state = make_train_state(model, jnp.zeros((2, 32, 32, 4), jnp.uint8))
    ev = make_eval_step()
    m = ev(state, _batch(b=2, h=32, w=32))
    assert np.isfinite(float(m["loss"])) and np.isfinite(float(m["px_err"]))


def test_corner_loss_normalization():
    pred = jnp.zeros((2, 8, 2))
    xy = jnp.full((2, 8, 2), 32.0)
    full = corner_loss(pred, xy, image_shape=(64, 64))
    np.testing.assert_allclose(float(full), 0.25, atol=1e-6)


def test_discriminator_and_policy_shapes():
    d = Discriminator(features=(8, 16))
    params = d.init(jax.random.key(0), jnp.zeros((2, 64, 64, 4), jnp.uint8))
    logits = d.apply(params, jnp.zeros((2, 64, 64, 4), jnp.uint8))
    assert logits.shape == (2,)
    p = PolicyValueNet(action_dim=1)
    pp = p.init(jax.random.key(0), jnp.zeros((3, 4)))
    mean, log_std, value = p.apply(pp, jnp.zeros((3, 4)))
    assert mean.shape == (3, 1) and log_std.shape == (1,) and value.shape == (3,)


def test_streamformer_with_ring_attention_on_mesh():
    mesh = create_mesh({"data": 2, "seq": 4})
    model = StreamFormer(
        patch=8, dim=32, depth=1, num_heads=4, use_ring=True, mesh=mesh
    )
    imgs = np.zeros((2, 32, 32, 4), np.uint8)  # 16 tokens / 4 seq shards
    sharding = NamedSharding(mesh, P("data"))
    imgs = jax.device_put(imgs, sharding)
    params = model.init(jax.random.key(0), imgs)["params"]
    out = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, imgs)
    assert out.shape == (2, 16)
    # equivalence: same params, ring vs plain attention
    plain = StreamFormer(patch=8, dim=32, depth=1, num_heads=4, use_ring=False)
    out2 = plain.apply({"params": params}, np.zeros((2, 32, 32, 4), np.uint8))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out2), atol=2e-2
    )


def test_checkpoint_save_restore(tmp_path):
    model = CubeRegressor(features=(8,))
    state = make_train_state(model, jnp.zeros((2, 32, 32, 4), jnp.uint8))
    step = make_supervised_step()
    state, _ = step(state, _batch(b=2, h=32, w=32))
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(int(state.step), state)
    assert mgr.latest_step() == 1
    fresh = make_train_state(model, jnp.zeros((2, 32, 32, 4), jnp.uint8))
    restored = mgr.restore(fresh)
    assert int(restored.step) == 1
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(restored.params)[0]),
        np.asarray(jax.tree.leaves(state.params)[0]),
    )
    mgr.close()


def test_image_ops():
    x = np.random.default_rng(0).integers(0, 255, (2, 8, 8, 4), np.uint8)
    n = normalize_uint8(jnp.asarray(x), jnp.float32)
    assert float(n.max()) <= 1.0
    g = gamma_correct(n, 2.2)
    assert g.shape == n.shape and float(g.min()) >= 0.0
    # the one-call form composes the two
    np.testing.assert_allclose(
        np.asarray(uint8_gamma_normalize(jnp.asarray(x))), np.asarray(g),
        atol=1e-6,
    )
    # flip augmentation flips exactly the samples the key's bernoulli bits
    # select (deterministic given the key)
    key = jax.random.key(0)
    xb = np.random.default_rng(1).integers(0, 255, (16, 4, 6, 3), np.uint8)
    f = np.asarray(random_flip(key, jnp.asarray(xb)))
    bits = np.asarray(jax.random.bernoulli(key, 0.5, (16,)))
    assert bits.any() and not bits.all()  # both behaviors exercised
    for i in range(16):
        expect = xb[i][:, ::-1] if bits[i] else xb[i]
        np.testing.assert_array_equal(f[i], expect)


def test_models_accept_prenormalized_floats():
    """uint8 and uint8/255-float inputs must agree (shared normalize
    guard; CubeRegressor once double-divided floats by 255)."""
    for model in (
        CubeRegressor(features=(8,)),
        Discriminator(features=(8,)),
        StreamFormer(patch=8, dim=32, depth=1, num_heads=4),
    ):
        x8 = np.random.default_rng(2).integers(0, 255, (2, 32, 32, 4), np.uint8)
        xf = (x8 / 255.0).astype(np.float32)
        params = model.init(jax.random.key(0), x8)
        np.testing.assert_allclose(
            np.asarray(model.apply(params, x8)),
            np.asarray(model.apply(params, xf)),
            atol=1e-2,
        )


def test_ring_attention_degrades_without_seq_axis():
    from blendjax.parallel import ring_attention
    from blendjax.parallel.ring import reference_attention

    mesh = create_mesh({"data": 8})
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 8, 2, 4)).astype(np.float32))
        for _ in range(3)
    )
    out = ring_attention(q, k, v, mesh)  # no 'seq' axis -> plain attention
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(reference_attention(q, k, v)), atol=1e-6
    )


def test_uint8_gamma_normalize_honors_gamma_and_dtype():
    """``gamma`` reaches the correction (gamma=1 is plain /255) and the
    result lands in the requested dtype."""
    x = np.random.default_rng(4).integers(0, 255, (1, 37, 8, 4), np.uint8)
    out = uint8_gamma_normalize(jnp.asarray(x), gamma=1.0, dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)), x / 255.0, atol=1e-2
    )
    brighter = uint8_gamma_normalize(jnp.asarray(x), gamma=2.2)
    assert float(jnp.mean(brighter)) > float(np.mean(x / 255.0))


def test_streamformer_remat_matches_baseline_grads():
    """remat=True (nn.remat blocks — recompute activations on backward)
    produces identical loss and gradients to the baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from blendjax.models import StreamFormer

    imgs = np.random.default_rng(0).integers(
        0, 255, (2, 32, 32, 4), np.uint8
    )
    kw = dict(patch=8, dim=32, depth=2, num_heads=4, num_outputs=4,
              dtype=jnp.float32)
    base = StreamFormer(**kw)
    rmt = StreamFormer(remat=True, **kw)
    params = base.init(jax.random.key(0), imgs)["params"]

    def loss(model, p):
        return jnp.mean(model.apply({"params": p}, imgs) ** 2)

    l0, g0 = jax.value_and_grad(lambda p: loss(base, p))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(rmt, p))(params)
    assert np.allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1,
    )


def test_gradient_accumulation_matches_full_batch():
    """accum_steps=4 produces (numerically) the same update as one full
    batch: mean-of-micro-losses and mean-of-micro-grads equal the
    full-batch values for a mean-reduced loss."""
    import jax
    import numpy as np

    from blendjax.models import CubeRegressor
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import make_supervised_step, make_train_state

    mesh = create_mesh({"data": -1})
    sh = batch_sharding(mesh)
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.integers(0, 255, (8, 32, 32, 4), np.uint8),
        "xy": rng.random((8, 8, 2), np.float32) * 32,
    }
    import optax

    # SGD: the update is linear in the gradients, so accumulated-vs-full
    # comparison isn't confounded by Adam's sign sensitivity at ~0 grads.
    s0 = make_train_state(
        CubeRegressor(), batch["image"], mesh=mesh,
        optimizer=optax.sgd(0.01),
    )
    step1 = make_supervised_step(mesh=mesh, batch_sharding=sh, donate=False)
    step4 = make_supervised_step(
        mesh=mesh, batch_sharding=sh, donate=False, accum_steps=4
    )
    s1, m1 = step1(s0, batch)
    s4, m4 = step4(s0, batch)
    assert np.allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6
        ),
        s1.params, s4.params,
    )
    with pytest.raises(ValueError, match="not divisible"):
        step3 = make_supervised_step(
            mesh=mesh, batch_sharding=sh, donate=False, accum_steps=3
        )
        step3(s0, batch)


def test_augmentation_ops_semantics():
    """On-device augmentation suite: static shapes/dtypes, per-sample
    randomness, and exact semantic checks per op."""
    from blendjax.ops.augment import (
        color_jitter,
        make_augment,
        random_crop,
        random_cutout,
        random_flip,
    )

    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, (8, 16, 24, 4), np.uint8)
    key = jax.random.key(7)

    flipped = np.asarray(jax.jit(random_flip)(key, imgs))
    assert flipped.shape == imgs.shape and flipped.dtype == np.uint8
    # every sample is either the original or its exact mirror
    per_sample = [
        (flipped[i] == imgs[i]).all()
        or (flipped[i] == imgs[i, :, ::-1]).all()
        for i in range(8)
    ]
    assert all(per_sample)
    assert any((flipped[i] != imgs[i]).any() for i in range(8))

    cropped = np.asarray(jax.jit(random_crop)(key, imgs))
    assert cropped.shape == imgs.shape and cropped.dtype == np.uint8

    jit_jitter = jax.jit(color_jitter)
    jittered = np.asarray(jit_jitter(key, imgs))
    assert jittered.shape == imgs.shape and jittered.dtype == np.uint8
    # identity-strength jitter is a no-op (round-trip through [0,1])
    ident = np.asarray(
        jax.jit(
            lambda k, x: color_jitter(k, x, brightness=0.0, contrast=0.0)
        )(key, imgs)
    )
    np.testing.assert_array_equal(ident, imgs)

    cut = np.asarray(jax.jit(random_cutout)(key, imgs))
    assert cut.shape == imgs.shape
    # each sample has a zeroed region (fill=0 over a square)
    assert all((cut[i] == 0).any() for i in range(8))

    aug = make_augment(random_flip, random_crop)
    out1 = np.asarray(jax.jit(aug)(key, imgs))
    out2 = np.asarray(jax.jit(aug)(key, imgs))
    np.testing.assert_array_equal(out1, out2)  # same key -> deterministic
    out3 = np.asarray(jax.jit(aug)(jax.random.key(8), imgs))
    assert (out3 != out1).any()


def test_supervised_step_with_on_device_augmentation():
    """augment= runs inside the jitted step, sharded with the batch, and
    the per-step key folds the step counter (deterministic across
    reruns; different across steps)."""
    import optax

    from blendjax.models import CubeRegressor
    from blendjax.ops.augment import make_augment, random_flip
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import make_supervised_step, make_train_state

    mesh = create_mesh({"data": -1})
    sh = batch_sharding(mesh)
    rng = np.random.default_rng(1)
    batch = {
        "image": jax.device_put(
            rng.integers(0, 255, (8, 32, 32, 4), np.uint8), sh
        ),
        "xy": jax.device_put(
            (rng.random((8, 8, 2)) * 32).astype(np.float32), sh
        ),
    }

    def make(seed):
        s0 = make_train_state(
            CubeRegressor(features=(8,)), np.asarray(batch["image"]),
            mesh=mesh, optimizer=optax.sgd(0.01),
        )
        step = make_supervised_step(
            mesh=mesh, batch_sharding=sh, donate=False,
            augment=make_augment(random_flip),
            augment_rng=jax.random.key(seed),
        )
        return s0, step

    s0, step = make(0)
    sA, mA = step(s0, batch)
    sA2, mA2 = step(s0, batch)
    assert float(mA["loss"]) == float(mA2["loss"])  # deterministic
    sB, mB = step(sA, batch)  # next step folds a different key
    assert np.isfinite(float(mB["loss"]))
    # a different augment seed gives a different trajectory
    s0c, stepc = make(123)
    _, mC = stepc(s0c, batch)
    assert np.isfinite(float(mC["loss"]))


def test_chunked_step_with_augment_matches_sequential():
    """make_chunked_supervised_step(augment=...) folds the in-scan step
    counter, so one scanned superbatch trains identically to K
    sequential per-batch augmented steps (same keys, same trajectory)."""
    import optax

    from blendjax.models import CubeRegressor
    from blendjax.ops.augment import make_augment, random_flip
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import (
        make_chunked_supervised_step,
        make_supervised_step,
        make_train_state,
    )

    mesh = create_mesh({"data": -1})
    sh = batch_sharding(mesh)
    rng = np.random.default_rng(7)
    K, B = 3, 4
    images = rng.integers(0, 255, (K, B, 32, 32, 4), np.uint8)
    xys = (rng.random((K, B, 8, 2)) * 32).astype(np.float32)
    aug = make_augment(random_flip)
    key = jax.random.key(42)
    s0 = make_train_state(
        CubeRegressor(features=(8,)), images[0], mesh=mesh,
        optimizer=optax.sgd(0.01),
    )

    seq = make_supervised_step(
        mesh=mesh, batch_sharding=sh, donate=False,
        augment=aug, augment_rng=key,
    )
    s_seq, seq_losses = s0, []
    for k in range(K):
        s_seq, m = seq(s_seq, {"image": images[k], "xy": xys[k]})
        seq_losses.append(float(m["loss"]))

    chunked = make_chunked_supervised_step(
        donate=False, augment=aug, augment_rng=key
    )
    s_chk, mc = chunked(s0, {"image": images, "xy": xys})

    np.testing.assert_allclose(np.asarray(mc["loss"]), seq_losses, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        s_seq.params, s_chk.params,
    )
    # sanity: the augment actually changed the trajectory vs no-augment
    plain = make_chunked_supervised_step(donate=False)
    _, mp = plain(s0, {"image": images, "xy": xys})
    assert not np.allclose(np.asarray(mp["loss"]), np.asarray(mc["loss"]))


def test_paired_geometric_augmentation_keeps_labels_synced():
    """random_flip_with_points / random_crop_with_points transform image
    and pixel-space labels together: a marker pixel's new location
    equals the transformed point, exactly."""
    from blendjax.ops.augment import (
        random_crop_with_points,
        random_flip_with_points,
    )

    b, h, w = 8, 16, 24
    imgs = np.zeros((b, h, w, 3), np.uint8)
    pts = np.empty((b, 1, 2), np.float32)  # (x, y)
    rng = np.random.default_rng(3)
    for i in range(b):
        y, x = int(rng.integers(0, h)), int(rng.integers(0, w))
        imgs[i, y, x] = 255
        pts[i, 0] = (x, y)

    key = jax.random.key(11)
    fi, fp = jax.jit(random_flip_with_points)(key, imgs, pts)
    fi, fp = np.asarray(fi), np.asarray(fp)
    flipped_any = False
    for i in range(b):
        ys, xs, _ = np.nonzero(fi[i])
        assert (xs[0], ys[0]) == (int(fp[i, 0, 0]), int(fp[i, 0, 1]))
        flipped_any |= (fi[i] != imgs[i]).any()
    assert flipped_any

    ci, cp = jax.jit(random_crop_with_points)(key, imgs, pts)
    ci, cp = np.asarray(ci), np.asarray(cp)
    assert ci.shape == imgs.shape
    moved_any = False
    for i in range(b):
        x2, y2 = cp[i, 0]
        if 0 <= x2 < w and 0 <= y2 < h:
            # marker may be duplicated by edge padding; the labeled
            # location must hold the marker value
            assert (ci[i, int(y2), int(x2)] == 255).all()
        moved_any |= (cp[i] != pts[i]).any()
    assert moved_any
