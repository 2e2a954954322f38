#!/usr/bin/env python
"""``olmohybrid_replay``'s prediction on the reference check's first
batch against its plain float32 reference, seed by seed, with the gated
delta rule as the kernel pair and as XLA's chunked form: what
``correct``'s loss comparison reads only through one projection of it.

    python scripts/output_vs_reference.py SEED [SEED ...]

For each seed: the cell's recording of that seed (made as ``run.py``
makes it, under ``benchmark/out/recordings``), the seeded parameters (the
cell's state program from ``jax.random.key(seed)``), and the first
message's frames through the production model twice, at the
configuration's precision as the step's forward computes it: as the
picker chooses (``kernel``: the kernel pair on a TPU) and with the picker
held to XLA's chunked form (``xla``: the program as it was before the
kernel pair), and through ``benchmark/references/StreamDeltaHybrid.py``
(float32, its calls under ``jax.default_matmul_precision("highest")``).
One JSON line a seed: each program's ``rel_l2`` (the difference's norm
over the reference's) and ``rel_max`` (the largest difference over the
reference's largest entry), and ``kernel_minus_xla_rel_l2``, the two
programs' distance from each other over the reference's norm.
"""

import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

WORKLOAD = "olmohybrid_replay"


def main(argv) -> int:
    seeds = [int(a) for a in argv]
    if not seeds:
        print(__doc__, file=sys.stderr)
        return 2

    import jax
    import numpy as np

    import cells
    import reference
    from blendjax.ops import delta_rule

    cell = cells.Cell(WORKLOAD)
    model = cell.model()
    make_state = cell.state_fn(model)
    forward = cells.load_module("references", cell.model_class()).forward
    kwargs = cell.config["model"]["kwargs"]

    def program():
        return jax.jit(lambda p, x: model.apply({"params": p}, x))

    picked, xla_form = program(), program()
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(lambda p, x: forward(p, x, **kwargs))
    messages = int(cell.traffic["reference_messages"])
    for seed in seeds:
        params = make_state(jax.random.key(seed)).params
        frames, _ = reference.decode_recording(
            cell.ensure_recording(seed, messages), 1)[0]
        got = {"kernel": np.asarray(picked(params, frames), np.float64)}
        # traced here, with the picker saying no
        with mock.patch.object(delta_rule, "auto_picks_kernel",
                               lambda *a, **k: False):
            got["xla"] = np.asarray(xla_form(params, frames), np.float64)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(plain(params, frames), np.float64)
        norm = np.linalg.norm(want)
        line = {"workload": WORKLOAD, "seed": seed}
        for form, out in got.items():
            line[form] = {
                "rel_l2": float(np.linalg.norm(out - want) / norm),
                "rel_max": float(np.abs(out - want).max()
                                 / np.abs(want).max()),
            }
        line["kernel_minus_xla_rel_l2"] = float(
            np.linalg.norm(got["kernel"] - got["xla"]) / norm)
        line["device"] = jax.devices()[0].device_kind
        print(json.dumps(line), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
