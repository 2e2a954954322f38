"""Rehearsal 3 of the on-chip-measurement guide: every cell's fused step
compiled at the real size for a *described* TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python benchmark/compile_rehearsal.py [--workload NAME]

For each cell it records the first chunk group of the cell's seeded
stream (real producer, real wire, here on the CPU), takes the packed
group's shapes from the real pipeline, and hands them with the abstract
train state to the TPU compiler: one device of a ``v5e:2x2`` for a
one-chip cell, the cell's mesh over all four for a four-chip cell. It
prints ``memory_analysis()`` per device, whether the Pallas decode
kernel is in the program, which collectives the compiler put in, and
what the reference check will hold at its peak (``reference_stage``).

Nothing runs on a TPU: what this prints are the compiler's byte counts,
never a time. It is what settles ``attn_backend``/``remat`` for a
configuration before chip time is spent (a program that does not fit 16
GB is refused here, not there).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

V5E_HBM_BYTES = 16 * 2**30


def reference_stage(cell, model, device) -> dict:
    """What the reference check will hold on ``device`` at its peak: the
    20 bytes a parameter of ``reference.reference_losses`` and the
    temporaries of its loss-and-gradient program over one micro-batch,
    float32 at ``highest``, as the TPU compiler counts them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import cells
    import reference

    placed = SingleDeviceSharding(device)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=placed),
        jax.eval_shape(cell.init_fn(model), jax.random.key(0)).params,
    )
    grad_fn = reference.loss_and_grad(
        cells.load_module("references", cell.model_class()).forward,
        cell.config["model"]["kwargs"], cell.reference_loss(),
    )
    rows = int(cell.config["reference_check"]["microbatch"])
    images = jax.ShapeDtypeStruct(
        (rows, *cell.shape, cell.channels), jnp.uint8, sharding=placed
    )
    xy = jax.ShapeDtypeStruct((rows, 8, 2), jnp.float32, sharding=placed)
    with jax.default_matmul_precision("highest"):
        ma = grad_fn.lower(params, images, xy).compile().memory_analysis()
    state = 20 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    stage = state + ma.temp_size_in_bytes
    return {
        "microbatch": rows, "state_bytes": state,
        "temp_bytes": ma.temp_size_in_bytes, "stage_bytes": stage,
        "fits_16GB": stage < V5E_HBM_BYTES,
    }


def rehearse(workload: str, seed: int, benchmark_json=None) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import cells

    cell = cells.Cell(workload, benchmark_json=benchmark_json)
    path = cell.ensure_recording(seed, cell.chunk)
    with cell.pipeline(path) as pipe:
        batch = next(iter(pipe))

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = cell.mesh(topo.devices[: cell.chips])
    placed = (
        SingleDeviceSharding(topo.devices[0]) if mesh is None
        else NamedSharding(mesh, PartitionSpec())
    )

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=placed)

    model = cell.model()
    state = jax.tree_util.tree_map(
        abstract, jax.eval_shape(cell.init_fn(model), jax.random.key(seed))
    )
    batch = dict(
        batch, _packed=abstract(batch["_packed"]),
        _refs={k: abstract(v) for k, v in batch["_refs"].items()},
    )
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # the code under test asks; take its TPU branch
    try:
        t0 = time.perf_counter()
        step = cell.make_step(state, mesh)
        compiled = cells.lower_fused(step, state, batch).compile()
        seconds = time.perf_counter() - t0
    finally:
        jax.default_backend = real_backend
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    per_device = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )
    params = sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(state.params)
    )
    return {
        "workload": workload, "chips": cell.chips,
        "model": cell.config["model"], "parameters": params,
        "group": [int(s) for s in batch["_packed"].shape],
        "compiled_for": "described v5e:2x2, nothing ran",
        "compile_seconds_here": round(seconds, 1),
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "per_device_bytes": per_device,
        "fits_16GB": per_device < V5E_HBM_BYTES,
        "reference_stage": reference_stage(cell, model, topo.devices[0]),
        "decode_kernel_in_program": "tpu_custom_call" in text,
        "collectives": sorted(
            c for c in ("all-reduce", "all-gather", "reduce-scatter",
                        "all-to-all", "collective-permute")
            if f"{c}(" in text or f"{c}-start(" in text
        ),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--benchmark-json", default=None,
                    help="another file in BENCHMARK.json's format")
    args = ap.parse_args(argv)

    import jax

    # a compile for a described device cannot be read back from the
    # persistent cache without the chip: keep it off here
    jax.config.update("jax_enable_compilation_cache", False)

    with open(
        args.benchmark_json or os.path.join(ROOT, "BENCHMARK.json")
    ) as f:
        names = args.workload or [
            w["name"] for w in json.load(f)["workloads"]
        ]
    ok = True
    for name in names:
        line = rehearse(name, args.seed, args.benchmark_json)
        ok &= line["fits_16GB"] and line["reference_stage"]["fits_16GB"]
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    from blendjax.launcher.launcher import kill_all_spawned

    try:
        code = main()
    finally:
        kill_all_spawned()
    sys.exit(code)
