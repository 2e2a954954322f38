"""Transformer training on a live producer stream.

The long-context layer meeting the data plane: `StreamFormer` (a
patch-embedding transformer regressing the same cube corners as
`CubeRegressor`) trains on streamed frames, sharded over whatever mesh
the host offers — batch over `data`, dense kernels over `tensor`
(Megatron-style), and, with a `seq` axis, exact ring attention rotating
K/V blocks around the ICI ring (`blendjax.parallel.ring`; Ulysses via
``--sp-mode ulysses``). No reference counterpart exists (the reference
has no sequence models, SURVEY.md §2.4); this composes blendjax's
net-new ICI plane with the reference-shaped streaming pipeline.

Run on one chip (mesh collapses to data=1):

    python examples/datagen/train_transformer.py --steps 20

Multi-chip shapes compile + execute on the virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/datagen/train_transformer.py \
        --steps 4 --mesh data=2,tensor=2,seq=2 --shape 64 64
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def parse_mesh(spec: str) -> dict:
    """'data=2,tensor=2,seq=2' -> {'data': 2, 'tensor': 2, 'seq': 2}
    ('data=-1' fills with the remaining devices)."""
    out = {}
    for part in spec.split(","):
        name, _, n = part.partition("=")
        out[name.strip()] = int(n)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--shape", nargs=2, type=int, default=[128, 128])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mesh", default="data=-1",
                    help="mesh axes, e.g. data=2,tensor=2,seq=2")
    ap.add_argument("--patch", type=int, default=16)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--sp-mode", choices=["ring", "ulysses"],
                    default="ring")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize blocks (HBM for FLOPs)")
    args = ap.parse_args()

    from blendjax.data import StreamDataPipeline
    from blendjax.launcher import PythonProducerLauncher
    from blendjax.models import StreamFormer
    from blendjax.parallel import batch_sharding, create_mesh
    from blendjax.train import (
        corner_loss,
        make_supervised_step,
        make_train_state,
    )

    axes = parse_mesh(args.mesh)
    mesh = create_mesh(axes)
    sharding = batch_sharding(mesh)
    h, w = args.shape
    model = StreamFormer(
        patch=args.patch, dim=args.dim, depth=args.depth,
        num_heads=args.heads, num_outputs=16,
        use_ring=mesh.shape.get("seq", 1) > 1,
        mesh=mesh, sp_mode=args.sp_mode, remat=args.remat,
    )
    state = make_train_state(
        model, np.zeros((args.batch, h, w, 4), np.uint8), mesh=mesh
    )

    def loss_fn(state, params, b):
        pred = state.apply_fn({"params": params}, b["image"])
        return corner_loss(pred.reshape(-1, 8, 2), b["xy"], image_shape=(h, w))

    step = make_supervised_step(
        mesh=mesh, batch_sharding=sharding, loss_fn=loss_fn
    )

    with PythonProducerLauncher(
        script=__file__.replace("train_transformer.py", "cube_producer.py"),
        num_instances=args.instances,
        named_sockets=["DATA"],
        seed=0,
        instance_args=[["--shape", str(h), str(w)]] * args.instances,
    ) as launcher:
        with StreamDataPipeline(
            launcher.addresses["DATA"],
            batch_size=args.batch,
            sharding=sharding,
        ) as pipe:
            t0, n = time.perf_counter(), 0
            for i, batch in enumerate(pipe):
                if i >= args.steps:
                    break
                state, metrics = step(
                    state, {"image": batch["image"], "xy": batch["xy"]}
                )
                n += batch["image"].shape[0]
                if i % 5 == 0:
                    print(f"step {i}: loss={float(metrics['loss']):.5f}")
            dt = time.perf_counter() - t0
            print(
                f"{n / dt:.1f} images/sec over mesh "
                f"{dict(mesh.shape)} ({n} images in {dt:.1f}s)"
            )


if __name__ == "__main__":
    main()
