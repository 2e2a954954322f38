"""The reference stage (ISSUE 27): the same losses as before to the last
bit, at most 20 bytes a parameter live at its peak, production parameters
that are the reference's, and a loss that comes from the loss's own file."""

import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import cells
import reference
from test_rehearse import last_line, run

CUBE = os.path.join(cells.HERE, "candidates", "cube_cells.json")
PROBE = os.path.join(cells.HERE, "candidates", "streamformer_d2048_l9.json")


def parent_reference_losses(forward, forward_kwargs, loss_of, tx, params,
                            batches, microbatch):
    """``reference_losses`` as it stood before PR 27, kept here word for
    word (nothing donated, the loss handed in): what "the same losses"
    is measured against."""
    def loss_fn(p, images, xy):
        return loss_of(
            forward(p, images, **forward_kwargs), xy, images.shape[1:3]
        )

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

    @jax.jit
    def update(p, opt_state, grad_sum, parts):
        grads = jax.tree_util.tree_map(lambda g: g / parts, grad_sum)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    losses = []
    with jax.default_matmul_precision("highest"):
        opt_state = tx.init(params)
        for images, xy in batches:
            parts = len(images) // microbatch
            total, grad_sum = 0.0, None
            for j in range(parts):
                sl = slice(j * microbatch, (j + 1) * microbatch)
                value, grads = grad_fn(params, images[sl], xy[sl])
                total += float(value)
                grad_sum = grads if grad_sum is None else add(grad_sum, grads)
            params, opt_state = update(params, opt_state, grad_sum, parts)
            losses.append(total / parts)
    return np.asarray(losses, np.float32)


def parent_corner_mse(pred, xy, hw):
    """``reference.corner_mse`` as it stood before PR 27."""
    h, w = hw
    scale = jnp.asarray([w, h], jnp.float32)
    return jnp.mean((pred.reshape(-1, 8, 2) / scale - xy / scale) ** 2)


def stage_inputs(cell, seed, microbatch=None):
    """What ``run.py`` hands the stage, at the cell's rehearsal size."""
    updates = int(cell.config["reference_check"]["updates"])
    recording = cell.ensure_recording(
        seed, int(cell.traffic.get("messages", updates))
    )
    return dict(
        forward=cells.load_module("references", cell.model_class()).forward,
        forward_kwargs=cell.config["model"]["kwargs"], tx=cell.optimizer(),
        batches=reference.decode_recording(recording, updates),
        microbatch=microbatch or int(cell.config["reference_check"]["microbatch"]),
    )


@pytest.fixture
def out(tmp_path, monkeypatch):
    monkeypatch.setattr(cells, "OUT", str(tmp_path))


@pytest.mark.parametrize("workload, listing", [
    ("vitb16_replay", None), ("cube_replay", CUBE), ("probe_replay", PROBE),
])
def test_the_rehearsals_give_the_parent_s_losses_to_the_last_bit(
    workload, listing, out
):
    cell = cells.Cell(workload, rehearse=True, benchmark_json=listing)
    model, seed = cell.model(), 7
    inputs = stage_inputs(cell, seed)
    was = parent_reference_losses(
        loss_of=parent_corner_mse, params=cell.make_state(model, seed).params,
        **inputs,
    )
    now = reference.reference_losses(
        loss_of=cell.reference_loss(), params=cell.make_state(model, seed).params,
        **inputs,
    )
    assert len(now) == 2 and now.dtype == np.float32
    assert now.tobytes() == was.tobytes()
    assert now[0] != now[1]  # the second loss saw an update


@pytest.mark.parametrize("microbatch, bytes_a_parameter", [(1, 20), (4, 16)])
def test_the_stage_holds_at_most_20_bytes_a_parameter(
    microbatch, bytes_a_parameter, out
):
    """Parameters 4 + moments 8 + gradient sum 4 + one micro-batch's
    gradient 4; with the whole batch in one micro-batch there is no sum.
    Counted with ``jax.live_arrays()`` at the peak, which ``watch`` marks;
    nothing the test itself holds is on the device."""
    cell = cells.Cell("vitb16_replay", rehearse=True)
    model = cell.model()
    inputs = stage_inputs(cell, 7, microbatch)
    params = cell.make_state(model, 7).params
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    before = reference.live_bytes() - 4 * n
    peaks = []
    reference.reference_losses(
        loss_of=cell.reference_loss(), params=params,
        watch=lambda: peaks.append(reference.live_bytes() - before),
        **inputs,
    )
    assert len(peaks) == 2 * (4 // microbatch)
    # the optimizer's step count and a loss: a few scalars besides
    assert bytes_a_parameter * n <= max(peaks) <= bytes_a_parameter * n + 64
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(params))
    del params
    assert reference.live_bytes() - before <= 64  # the stage left nothing


@pytest.mark.parametrize("workload, devices, listing", [
    ("vitb16_replay", 1, None), ("vitb16_mesh4", 4, None),
    ("probe_replay", 1, PROBE),
])
def test_a_run_s_stage_is_alone_and_production_starts_where_it_did(
    workload, devices, listing
):
    """Through ``run.py``: every live array of the process at the stage's
    peak is within the budget (so no production state is there yet), and
    the production state made afterwards, on one device or sharded over a
    4-device mesh, holds the parameters the reference started from."""
    extra = ["--benchmark-json", listing] if listing else []
    proc = run(
        ["--workload", workload, "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearse", *extra], devices=devices,
    )
    line = last_line(proc)
    ref = next(
        json.loads(s) for s in proc.stdout.splitlines()
        if s.startswith('{"phase": "reference"')
    )
    stage = ref["stage"]
    assert stage["parameters"] > 20_000
    assert stage["live_peak_bytes"] <= 20 * stage["parameters"] + 128
    assert stage["live_bytes_after"] <= 128
    assert ref["seeded_parameters"] is True and ref["ok"] is True
    assert line["correct"] is True
    assert list(line)[-1] == "compared"  # each number beside its limit, last
    assert line["compared"]["seeded_leaves_differing"] == [0, 0]
    assert line["compared"]["loss_rel_diff"] == [ref["max_rel_diff"], ref["rtol"]]
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [t.split(":")[0] for t in tail] == [
        f"compared {k}" for k in line["compared"]
    ]


def test_the_checksum_is_exact_and_order_free():
    key = jax.random.key(0)
    tree = {"a": jax.random.normal(key, (64, 48)), "b": jnp.arange(5.0)}
    base = reference.parameter_checksum(tree)
    assert base.dtype == np.uint32 and base.shape == (2,)
    assert (reference.parameter_checksum(jax.device_get(tree)) == base).all()
    one_ulp = dict(tree, a=tree["a"].at[3, 5].set(
        jnp.nextafter(tree["a"][3, 5], jnp.inf)
    ))
    assert (reference.parameter_checksum(one_ulp) != base).tolist() == [True, False]
    swapped = dict(tree, a=tree["a"].at[0].set(tree["a"][1]).at[1].set(tree["a"][0]))
    assert reference.parameter_checksum(swapped)[0] != base[0]


def test_a_different_production_state_fails_the_condition(out):
    """What ``seeded_parameters`` compares: a state from another seed, or
    one leaf nudged, differs in its checksum."""
    cell = cells.Cell("vitb16_replay", rehearse=True)
    model = cell.model()
    seeded = reference.parameter_checksum(cell.make_state(model, 3).params)
    same = reference.parameter_checksum(cell.make_state(model, 3).params)
    other = reference.parameter_checksum(cell.make_state(model, 4).params)
    assert (same == seeded).all()
    assert (other != seeded).sum() >= 4  # biases and scales start alike


def test_a_loss_of_its_own_needs_only_its_own_file(tmp_path, monkeypatch):
    """A toy loss file, found by name, checked through ``reference_losses``
    against a hand computation; ``reference.py`` names no loss."""
    (tmp_path / "losses").mkdir()
    (tmp_path / "losses" / "toy_l1.py").write_text(textwrap.dedent('''
        def loss_fn(state, params, batch):
            raise NotImplementedError("production's form is not under test")


        def reference_loss(pred, labels, hw):
            import jax.numpy as jnp

            return jnp.mean(jnp.abs(pred - labels.reshape(len(labels), -1)))
    '''))
    monkeypatch.setattr(cells, "HERE", str(tmp_path))
    loss_of = cells.load_module("losses", "toy_l1").reference_loss
    with open(reference.__file__) as f:
        source = f.read()
    assert "corner_mse" not in source and "REFERENCE_LOSSES" not in source

    def forward(p, images, *, gain):
        return gain * images.astype(jnp.float32).mean(axis=(1, 2)) @ p["w"]

    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (4, 6, 6, 3), dtype=np.uint8)
    labels = rng.normal(size=(4, 1, 2)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(3, 2)), jnp.float32)
    got = reference.reference_losses(
        forward, {"gain": 0.5}, loss_of, optax.sgd(1e-4), {"w": jnp.array(w)},
        [(images, labels), (images, labels)], microbatch=2,
    )

    def by_hand(w):
        pred = 0.5 * images.astype(np.float32).mean(axis=(1, 2)) @ w
        return np.abs(pred - labels.reshape(4, -1))

    first = by_hand(np.asarray(w))
    assert got[0] == pytest.approx(first.mean(), rel=1e-6)
    grad = jax.grad(lambda w: jnp.mean(jnp.abs(
        0.5 * jnp.asarray(images, jnp.float32).mean(axis=(1, 2)) @ w
        - labels.reshape(4, -1)
    )))(w)
    assert got[1] == pytest.approx(
        by_hand(np.asarray(w - 1e-4 * grad)).mean(), rel=1e-5
    )
    assert got[1] < got[0]
