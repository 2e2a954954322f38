"""Counts made inside the step: the expert layer's picks, out of the step,
into the registry.

``RoutedExperts`` sows three integers a call (``rows_held``,
``rows_busiest_share``, ``rows_even_share``) into the ``counters``
collection; the step builders return them summed over the layers and a
dispatch's updates beside the loss (``train.steps.counting``), and
``TrainDriver`` books them as ``moe.rows_*`` when the dispatch retires.
Here: the sown counts are a NumPy count of the same ``top_k`` picks,
with and without ``remat``, whose backward does not count again; every
update of a driven stream is booked exactly once, with no extra host
wait; a model that counts nothing compiles to the program it did before.
"""

import re

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from blendjax.models import RoutedExperts, StreamFormer, StreamHybrid
from blendjax.train import (
    TrainDriver,
    corner_loss,
    make_chunked_supervised_step,
    make_fused_tile_step,
    make_supervised_step,
    make_train_state,
)
from blendjax.utils.metrics import metrics as reg

LAYER = dict(num_experts=32, experts_per_token=6, expert_width=12,
             shared_width=20, scaling=2.5, dtype=jnp.float32)


def numpy_counts(x, params, num_experts, k, held, offset):
    """The three counts of one expert layer's picks on ``x`` (B, T, C),
    counted in NumPy from the same ``top_k`` the layer takes (largest
    score plus selection bias, the lower index first on a tie)."""
    tokens = jnp.asarray(x, jnp.float32).reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(jnp.dot(
        tokens, params["router"], precision=jax.lax.Precision.HIGHEST,
    )) + params["e_score_correction_bias"]
    picks = np.argsort(-np.asarray(scores), axis=1, kind="stable")[:, :k]
    per_expert = np.bincount(picks.ravel(), minlength=num_experts)
    shares = -(-num_experts // held)
    per_share = np.pad(per_expert, (0, shares * held - num_experts))
    return {
        "rows_held": int(per_expert[offset:offset + held].sum()),
        "rows_busiest_share": int(
            per_share.reshape(shares, held).sum(axis=1).max()
        ),
        "rows_even_share": picks.size * held // num_experts,
    }


def sown_totals(cols):
    """``{name: sum}`` over a ``counters`` collection's sown tuples."""
    return {
        name: int(sum(np.asarray(v) for v in values))
        for name, values in cols["counters"].items()
    }


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("held, offset", [
    (32, 0),   # the whole layer: one share, the busiest is every pick
    (4, 0),    # 8 chips' first share
    (6, 4),    # a share that is no block of 6: the blocks stay 0-5, 6-11, ...
])
def test_the_sown_counts_are_a_numpy_count_of_the_picks(held, offset, remat):
    x = jax.random.normal(jax.random.key(6), (2, 11, 16))
    cls = nn.remat(RoutedExperts) if remat else RoutedExperts
    layer = cls(**LAYER, experts_held=held, expert_offset=offset)
    params = layer.init(jax.random.key(7), x)["params"]
    params["e_score_correction_bias"] = 0.05 * jax.random.normal(
        jax.random.key(8), (32,)
    )

    def loss(p):
        y, cols = layer.apply({"params": p}, x, mutable=["counters"])
        return jnp.sum(y ** 2), cols

    grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
    (_, cols), _ = grad(params)
    assert sown_totals(cols) == numpy_counts(x, params, 32, 6, held, offset)
    # the forward counts once; the backward, recomputed or not, never
    text = grad.lower(params).as_text()
    assert len(re.findall(
        r"reduce\(.*dimensions = \[1, 2\] : \(tensor<32x6x22xi32>", text
    )) == 1
    # where the collection is not asked for, apply returns the output alone
    assert layer.apply({"params": params}, x).shape == (2, 11, 16)


# -- through the step and the driver -------------------------------------------

# the nemotron3nano_replay rehearsal's StreamHybrid (benchmark/configs/
# nemotron3_nano_30b_a3b.json "rehearse"), in float32
REHEARSAL = dict(
    patch=8, dim=32, pattern="MEMEM*EME", mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=8, n_groups=2, conv_kernel=4, chunk_size=16, num_heads=4,
    num_kv_heads=2, head_dim=16, num_experts=16, experts_per_token=3,
    expert_width=24, shared_width=48, routed_scaling=2.5, experts_held=2,
    expert_offset=0, norm_eps=1e-5, num_outputs=16, remat=True,
    dtype=jnp.float32,
)
E_LAYERS = REHEARSAL["pattern"].count("E")
H = W = 32
TOKENS = (H // 8) * (W // 8)


def corner_mse(state, params, batch):  # benchmark/losses/corner_mse.py
    pred = state.apply_fn({"params": params}, batch["image"])
    return corner_loss(pred.reshape(-1, 8, 2), batch["xy"],
                       image_shape=batch["image"].shape[1:3])


def model_counts(params, images):
    """The NumPy count of every expert layer's picks on ``images``,
    summed: each layer's input is the norm in front of it."""
    plain = StreamHybrid(**{**REHEARSAL, "remat": False})
    _, cols = plain.apply({"params": params}, images,
                          capture_intermediates=True, mutable=["intermediates"])
    total: dict = {}
    for i, kind in enumerate(REHEARSAL["pattern"]):
        if kind != "E":
            continue
        x = cols["intermediates"][f"layer{i}"]["norm"]["__call__"][0]
        for name, n in numpy_counts(
            x, params[f"layer{i}"]["mixer"], 16, 3, 2, 0
        ).items():
            total[name] = total.get(name, 0) + n
    return total


def _pal_messages(frames, xys):
    from blendjax.ops.tiles import (
        FRAMEPAL_SUFFIXES,
        FRAMESHAPE_SUFFIX,
        PALETTE_SUFFIX,
        palettize_frames,
    )

    for g in range(len(xys)):
        packed, pal, bits = palettize_frames(frames[2 * g: 2 * g + 2])
        yield {
            "_prebatched": True, "btid": 0,
            "image" + FRAMEPAL_SUFFIXES[bits]: packed,
            "image" + PALETTE_SUFFIX: pal,
            "image" + FRAMESHAPE_SUFFIX: np.array([H, W, 4, bits], np.int32),
            "xy": xys[g],
        }


def test_the_driver_books_every_updates_counts_once():
    """Four chunk groups of two updates through the fused step and the
    driver: the registry's counts after ``drain()`` are the sum of the
    NumPy count over the eight updates (``sgd(0)`` keeps the parameters
    the count is made with), and booking them adds no loss fetch."""
    from blendjax.data import StreamDataPipeline

    rng = np.random.default_rng(11)
    colors = rng.integers(0, 255, (5, 4), np.uint8)
    frames = colors[rng.integers(0, 5, (16, H, W))]
    xys = (rng.random((8, 2, 8, 2)) * 32).astype(np.float32)
    s0 = make_train_state(StreamHybrid(**REHEARSAL), frames[:2],
                          optimizer=optax.sgd(0.0))
    reg.reset()
    drv = TrainDriver(make_fused_tile_step(loss_fn=corner_mse, donate=False),
                      s0, inflight=2, sync_every=3)
    with StreamDataPipeline(_pal_messages(frames, xys), batch_size=2,
                            chunk=2, emit_packed=True) as pipe:
        drv.run(pipe)
    updates = 8
    assert drv.stats["steps"] == 4 and drv.images_retired == 2 * updates
    report = reg.report()
    counters = report["counters"]
    assert counters["moe.rows_even_share"] == (
        updates * E_LAYERS * (2 * TOKENS) * 3 * 2 // 16
    )
    want = [model_counts(s0.params, frames[2 * u: 2 * u + 2])
            for u in range(updates)]
    for name in ("rows_held", "rows_busiest_share", "rows_even_share"):
        assert counters[f"moe.{name}"] == sum(w[name] for w in want), name
    # the periodic fetch alone: one every `sync_every` dispatches
    assert report["spans"]["driver.loss_sync"]["count"] == 4 // 3


@pytest.mark.parametrize("build, calls", [
    (lambda: make_supervised_step(loss_fn=corner_mse, donate=False),
     lambda im: [im[0]]),
    (lambda: make_supervised_step(loss_fn=corner_mse, donate=False,
                                  accum_steps=2),
     lambda im: [im[0][:1], im[0][1:]]),
    (lambda: make_chunked_supervised_step(loss_fn=corner_mse, donate=False),
     list),
], ids=["supervised", "accumulated", "chunked"])
def test_every_builder_returns_the_counts_summed(build, calls):
    """Beside the loss: the counts of every call of the model in the
    step, summed: one update's, two microbatches' (each its own busiest
    share), a scan's two updates'."""
    rng = np.random.default_rng(12)
    images = rng.integers(0, 255, (2, 2, H, W, 4), np.uint8)
    xy = (rng.random((2, 2, 8, 2)) * 32).astype(np.float32)
    state = make_train_state(StreamHybrid(**REHEARSAL), images[0],
                             optimizer=optax.sgd(0.0))
    chunked = calls is list
    batch = ({"image": images, "xy": xy} if chunked
             else {"image": images[0], "xy": xy[0]})
    _, m = build()(state, batch)
    assert set(m) == {"loss", "counters"}
    assert {k: v.dtype for k, v in m["counters"].items()} == dict.fromkeys(
        ("rows_held", "rows_busiest_share", "rows_even_share"), jnp.int32
    )
    want = [model_counts(state.params, im) for im in calls(images)]
    for name, n in m["counters"].items():
        assert int(n) == sum(w[name] for w in want), name


# -- a model that counts nothing -------------------------------------------------


@pytest.mark.parametrize("build", [
    make_supervised_step, make_chunked_supervised_step,
], ids=["supervised", "chunked"])
def test_a_model_that_counts_nothing_compiles_as_before(build):
    """StreamFormer sows nothing: its step lowers to the same text as with
    the capture out of the way (an ``apply_fn`` that is no flax module's
    is not captured), and returns the loss alone."""
    model = StreamFormer(patch=8, dim=32, depth=2, num_heads=4,
                         num_outputs=16)
    rng = np.random.default_rng(13)
    images = rng.integers(0, 255, (2, 2, H, W, 4), np.uint8)
    xy = (rng.random((2, 2, 8, 2)) * 32).astype(np.float32)
    batch = ({"image": images, "xy": xy}
             if build is make_chunked_supervised_step
             else {"image": images[0], "xy": xy[0]})
    state = make_train_state(model, images[0], optimizer=optax.adamw(1e-3))
    plain = state.replace(apply_fn=lambda v, x: model.apply(v, x))
    step = build(loss_fn=corner_mse, donate=False)
    assert step.lower(state, batch).as_text() == (
        step.lower(plain, batch).as_text()
    )
    _, m = step(state, batch)
    assert set(m) == {"loss"}
