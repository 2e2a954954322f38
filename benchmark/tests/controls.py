"""The control and the faults the reference comparison must fail (the
contract's "How correct is decided", steps 2 to 4), each planted in the
plain reference put in the program's place, so that one chip and one
compiled program read them all at the cell's own size:

- ``fp8_weights``: the control: the reference with every matrix rounded to
  float8 e4m3 on its way into the forward pass, the nearest precision below
  the bf16 the configuration states;
- ``state_unchanged``: a step that returns its state as it got it: the
  second update's loss is taken from the first's parameters;
- ``half_batch``: half of the rows left out, the mean taken over the rest;
- ``no_exchange``: a mesh step whose gradient exchange is left out: the
  update is made from one chip's quarter of the rows alone.

Sound readings are production's own, from the runs. ``readings(cell,
seed)`` gives, for each plant, what ``reference.compare`` would
read against the sound reference: the largest relative difference over the
per-update losses. On the chip: ``python3 benchmark/tests/controls.py
--workload vitb16_replay --seeds 1 2 3`` (writes nothing; prints one line a
seed)."""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cells  # noqa: E402
import reference  # noqa: E402


def fp8_weights(forward):
    """``forward`` with every matrix of its parameters rounded to float8
    e4m3 (3 bits of mantissa) and back on the way in; the gradient passes
    straight through the rounding."""
    import jax
    import jax.numpy as jnp

    def rounded(p, images, **kwargs):
        return forward(jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            if x.ndim >= 2 else x, p,
        ), images, **kwargs)

    return rounded


def readings(cell, seed: int) -> dict:
    """``{fault: largest relative difference of its per-update losses
    from the sound reference's}`` at the cell's size, 2 updates."""
    model = cell.model()
    recording = cell.ensure_recording(seed, 2)
    batches = reference.decode_recording(recording, 2)
    forward = cells.load_module("references", cell.model_class()).forward
    rows = int(cell.config["reference_check"]["microbatch"])

    def losses(batches, forward=forward, rows=rows):
        return reference.reference_losses(
            forward, cell.config["model"]["kwargs"], cell.reference_loss(),
            cell.optimizer(), cell.make_state(model, seed).params, batches, rows,
        ).astype(np.float64)

    def rel(got, want):
        return float(np.max(np.abs(got - want) / np.abs(want)))

    sound = losses(batches)
    (im0, xy0), (im1, xy1) = batches
    half, quarter = len(im0) // 2, max(len(im0) // 4, rows)
    # what one update's gradient came from decides the second loss only
    no_exchange = losses([(im0[:quarter], xy0[:quarter]), (im1, xy1)])
    return {
        "fp8_weights": rel(losses(batches, fp8_weights(forward)), sound),
        # the second batch's loss from the first update's parameters: the
        # first loss of a run that starts at the second batch
        "state_unchanged": rel(losses([(im1, xy1)])[0], sound[1]),
        "half_batch": rel(
            losses([(im0[:half], xy0[:half]), (im1[:half], xy1[:half])]),
            sound,
        ),
        "no_exchange": rel(no_exchange[1], sound[1]),
        "loss": [float(v) for v in sound],
    }


def plant(fault: str) -> None:
    """The same faults planted in the program underneath the harness (and
    ``loss_altered``: an answer altered where it is produced), for a test
    that drives a whole run and sees ``correct`` come out false. The
    reference still gets the sound optimizer and loss."""
    import jax
    import optax

    if fault == "state_unchanged":
        init_fn = cells.Cell.init_fn

        def unchanged(self, model):
            init = init_fn(self, model)
            return lambda key: init(key).replace(tx=optax.set_to_zero())

        cells.Cell.init_fn = unchanged
        return
    loss_fn = cells.Cell.loss_fn

    def broken(self):
        loss = loss_fn(self)

        def over(state, params, batch, share):
            rows = max(len(batch["image"]) // share, 1)
            return loss(state, params, {
                **batch, "image": batch["image"][:rows], "xy": batch["xy"][:rows],
            })

        def no_exchange(state, params, batch):
            # the whole batch's loss, one chip's quarter's gradient
            mine = over(state, params, batch, 4)
            return mine + jax.lax.stop_gradient(loss(state, params, batch) - mine)

        return {
            "half_batch": lambda s, p, b: over(s, p, b, 2),
            "no_exchange": no_exchange,
            "loss_altered": lambda s, p, b: 1.001 * loss(s, p, b),
        }[fault]

    cells.Cell.loss_fn = broken


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark-json", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform == "cpu" and not args.rehearse:
        print("no accelerator: nothing was run", file=sys.stderr)
        return 3
    cell = cells.Cell(args.workload, args.rehearse, args.benchmark_json)
    for seed in args.seeds:
        print(json.dumps({
            "workload": cell.name, "seed": seed, "rtol": cell.config[
                "reference_check"].get("rtol"),
            "platform": jax.devices()[0].platform, **readings(cell, seed),
        }), flush=True)
    return 0


if __name__ == "__main__":
    from blendjax.launcher.launcher import kill_all_spawned

    try:
        code = main()
    finally:
        kill_all_spawned()
    sys.exit(code)
