"""One cell of BENCHMARK.json, resolved from data files into the objects
the program's own entry points take.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own that is found by name:

    configs/<config>.json      model class + kwargs, loss, optimizer,
                               precision, driver arguments, loss band
    traffic/<traffic>.json     kind (live | replay), producer arguments,
                               message batch, counts
    losses/<loss>.py           ``loss_fn(state, params, batch)`` and its
                               plain float32 form for the reference,
                               ``reference_loss(prediction, labels, (h, w))``
    references/<model>.py      the plain float32 forward of that model
    flops/<model>.py           required operations from shapes
    flops/kernels/<family>.py  the work a kernel's call requires, from
                               the same shapes, for its roofline share
    layer_metrics/<metric>.json + readers/<reader>.py

so a later PR adds a cell by adding files and entries, never by editing
this one. From the program this module takes only its public entry
points: ``make_fused_tile_step``, ``TrainDriver``/``MeshTrainDriver``,
``StreamDataPipeline`` and ``PythonProducerLauncher``.
"""

from __future__ import annotations

import hashlib
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")

# Recordings kept in a checkout (90 MB each at 2,048 frames): a run with a
# seed not seen before makes one, and the oldest beyond this many go.
KEEP_RECORDINGS = 4

# Dispatches before the window. The donated fused step compiles twice
# (its second call sees the first one's output layouts; chip_smoke.py
# learned it on the chip), and both are set-up. Only the program can
# make it one.
WARMUP_STEPS = 2


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by file name
    (the directories are registries, not packages: ``flops.py`` and
    ``flops/`` live side by side)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("/", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)
        ) else v
    return out


class Cell:
    """A workload entry with its configuration and traffic files read.
    ``rehearse`` applies each file's ``rehearse`` block (a tiny size for
    the CPU) over it; the real sizes are what the chip gets."""

    def __init__(self, workload: str, rehearse: bool = False,
                 benchmark_json: str | None = None):
        with open(benchmark_json or os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)
        entry = next(
            (w for w in self.benchmark["workloads"] if w["name"] == workload),
            None,
        )
        if entry is None:
            names = [w["name"] for w in self.benchmark["workloads"]]
            raise SystemExit(f"unknown workload {workload!r}; have {names}")
        self.name = workload
        self.chips = int(entry["chips"])
        cfg_entry = next(
            c for c in self.benchmark["configs"] if c["name"] == entry["config"]
        )
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", f"{entry['traffic']}.json")
        self.rehearse = bool(rehearse)
        if rehearse:
            self.config = _merge(self.config, self.config.get("rehearse", {}))
            self.traffic = _merge(
                self.traffic, self.traffic.get("rehearse", {})
            )

    # -- what the cell reports -------------------------------------------------

    def metrics(self, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [
            m for m in self.benchmark[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    # -- sizes -------------------------------------------------------------------

    @property
    def shape(self) -> tuple:
        return tuple(self.config["input"]["shape"])

    @property
    def channels(self) -> int:
        return int(self.config["input"]["channels"])

    @property
    def batch(self) -> int:
        """Images per optimizer update = per wire message."""
        return int(self.traffic["batch"])

    @property
    def chunk(self) -> int:
        return int(self.config["chunk"])

    # -- the stream --------------------------------------------------------------

    def producer_args(self) -> list:
        h, w = self.shape
        return [
            "--shape", str(h), str(w), "--batch", str(self.batch),
            *[str(a) for a in self.traffic["producer_args"]],
        ]

    def scene_script(self) -> str:
        return os.path.join(HERE, "traffic", self.traffic["script"])

    def recording_prefix(self, seed: int, messages: int) -> str:
        """Where this cell's seeded recording is kept: the name holds
        everything that decides its bytes, so a second run of the cell
        in the checkout finds it and a changed parameter never does."""
        key = json.dumps(
            [seed, messages, self.producer_args(), self.traffic["script"]]
        )
        digest = hashlib.sha256(key.encode()).hexdigest()[:12]
        return os.path.join(OUT, "recordings", f"{digest}-s{seed}-n{messages}")

    def ensure_recording(self, seed: int, messages: int) -> str:
        """One producer's first ``messages`` messages (the reference
        frame rides the first), teed raw off the wire to a ``.bjr``."""
        prefix = self.recording_prefix(seed, messages)
        path = f"{prefix}_00.bjr"
        if os.path.exists(path):
            return path
        from blendjax.data.stream import RemoteStream
        from blendjax.launcher import PythonProducerLauncher

        os.makedirs(os.path.dirname(prefix), exist_ok=True)
        tmp = f"{prefix}.part"
        with PythonProducerLauncher(
            script=self.scene_script(), num_instances=1,
            named_sockets=["DATA"], seed=seed, proto="ipc",
            instance_args=[self.producer_args()],
        ) as launcher:
            stream = RemoteStream(
                launcher.addresses["DATA"], timeoutms=60_000,
                max_items=messages, record_path_prefix=tmp,
            )
            n = sum(1 for _ in stream)
        if n != messages:
            raise RuntimeError(f"recorded {n} of {messages} messages")
        os.replace(f"{tmp}_00.bjr", path)  # never a half-written recording
        kept = sorted(
            (os.path.join(os.path.dirname(path), f)
             for f in os.listdir(os.path.dirname(path)) if f.endswith(".bjr")),
            key=os.path.getmtime,
        )
        for old in kept[:-KEEP_RECORDINGS]:
            os.remove(old)
        return path

    def launcher(self, seed: int):
        """The live traffic's producers (not yet started)."""
        from blendjax.launcher import PythonProducerLauncher

        n = int(self.traffic["producers"])
        return PythonProducerLauncher(
            script=self.scene_script(), num_instances=n,
            named_sockets=["DATA"], seed=seed, proto="ipc",
            instance_args=[self.producer_args()] * n,
        )

    def pipeline(self, source, mesh=None, launcher=None, loop=False):
        """``StreamDataPipeline`` over producer addresses (live) or a
        recording (replay), packed groups for the fused step."""
        from blendjax.data import StreamDataPipeline

        kwargs = dict(
            batch_size=self.batch, chunk=self.chunk, emit_packed=True,
            mesh=mesh,
        )
        if launcher is not None:
            return StreamDataPipeline(
                source, launcher=launcher, timeoutms=60_000, **kwargs
            )
        return StreamDataPipeline.from_recording(source, loop=loop, **kwargs)

    # -- the model ---------------------------------------------------------------

    def model(self):
        spec = self.config["model"]
        module, _, cls = spec["class"].rpartition(".")
        return getattr(importlib.import_module(module), cls)(**spec["kwargs"])

    def model_class(self) -> str:
        return self.config["model"]["class"].rpartition(".")[2]

    def loss_fn(self):
        return load_module("losses", self.config["loss"]).loss_fn

    def reference_loss(self):
        return load_module("losses", self.config["loss"]).reference_loss

    def optimizer(self):
        import optax

        spec = dict(self.config["optimizer"])
        return getattr(optax, spec.pop("name"))(**spec)

    def init_fn(self, model):
        """``key -> TrainState``: parameters and optimizer state in one
        traceable function, so the whole state is made on the device in
        one jitted call from the seed."""
        import jax.numpy as jnp
        from flax.training.train_state import TrainState

        tx = self.optimizer()
        shape = (self.batch, *self.shape, self.channels)

        def init(key):
            params = model.init(key, jnp.zeros(shape, jnp.uint8))["params"]
            return TrainState.create(
                apply_fn=model.apply, params=params, tx=tx
            )

        return init

    def mesh(self, devices):
        """The cell's mesh over ``devices``; ``None`` on one chip."""
        if self.chips == 1:
            return None
        from blendjax.parallel import create_mesh
        from blendjax.parallel.sharding import resolve_layout

        layout = resolve_layout(self.config["layout_4chips"])
        return create_mesh(layout.mesh_axes(), devices=list(devices))

    def state_fn(self, model, mesh=None):
        """``init_fn`` jitted for one chip or, with the layout's
        shardings, for ``mesh``. One program: called twice with one key it
        gives the same state twice, which is how the reference and
        production start from the same parameters without both being on
        the device at once."""
        import jax

        from blendjax.parallel.sharding import resolve_rules, state_shardings

        init = self.init_fn(model)
        if mesh is None:
            return jax.jit(init)
        layout = self.config["layout_4chips"]
        shardings = state_shardings(
            jax.eval_shape(init, jax.random.key(0)), mesh=mesh,
            rules=resolve_rules(layout=layout, model=model),
        )
        return jax.jit(init, out_shardings=shardings)

    def make_state(self, model, seed: int, mesh=None):
        import jax

        return self.state_fn(model, mesh)(jax.random.key(seed))

    def make_step(self, state, mesh=None):
        """The fused decode+step the cell dispatches."""
        if mesh is None:
            from blendjax.train import make_fused_tile_step

            return make_fused_tile_step(loss_fn=self.loss_fn())
        from blendjax.train.mesh_driver import make_mesh_fused_step

        return make_mesh_fused_step(state, mesh, loss_fn=self.loss_fn())

    def make_driver(self, step, state, mesh=None):
        args = self.config["driver"]
        if mesh is None:
            from blendjax.train import TrainDriver

            return TrainDriver(step, state, **args)
        from blendjax.parallel.sharding import resolve_layout
        from blendjax.train import MeshTrainDriver

        driver = MeshTrainDriver(step, state, mesh, **args)
        driver.layout = resolve_layout(self.config["layout_4chips"]).name
        return driver


def lower_fused(step, state, batch):
    """The fused tile step lowered for ``batch`` (a packed tile group)."""
    return step.jits["tile"].lower(
        state, batch["_packed"], batch["_refs"], batch["_spec"],
        batch["_names"], batch["_geoms"], batch.get("_rle", ()),
    )
