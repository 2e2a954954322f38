"""The step's parts carry their names where they run.

A profiler trace says of every device operation which name stack it was
traced under (``op_name`` in the compiled HLO, ``tf_op`` in the trace),
and ``benchmark/trace_scopes.py`` reads each part's device time from
that. So the names are a contract: ``decode`` (with ``palette_expand``
and the Pallas kernels inside it), ``reshard``, ``optimizer`` and
``attn_core`` from ``blendjax.utils.metrics.STEP_SCOPES``; forward and
backward are ``jvp(<Model>)`` and ``transpose(jvp(<Model>))``, which
flax and ``jax.grad`` write themselves. Everything here compiles for the
CPU backend at a tiny size; ``tests/test_tpu_compile.py`` asks the chip's
compiler the same of the kernel. And the host side of the same account:
``TrainDriver.drain()`` books its blocking fetch as ``driver.drain_wait``,
and every span of the registry is a ``TraceAnnotation`` of the same name,
so a profiler trace shows the program's spans over the device's work.
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from blendjax.models import CubeRegressor, StreamFormer
from blendjax.ops import tiles as T
from blendjax.train import (
    TrainDriver,
    corner_loss,
    make_chunked_supervised_step,
    make_echo_fused_step,
    make_fused_tile_step,
    make_supervised_step,
    make_train_state,
)
from blendjax.train.mesh_driver import MeshTrainDriver
from blendjax.utils.metrics import (
    KERNEL_FLASH_BWD,
    KERNEL_FLASH_FWD,
    KERNEL_NAMES,
    KERNEL_TILE_DECODE_SCATTER,
    KERNEL_TILE_DECODE_SPATIAL,
    SCOPE_ATTN_CORE,
    SCOPE_DECODE,
    SCOPE_OPTIMIZER,
    SCOPE_PALETTE_EXPAND,
    SCOPE_PATCH_EMBED,
    STEP_SCOPES,
)
from blendjax.utils.metrics import metrics as reg

H, W, C = 64, 128, 4
B, CHUNK = 2, 2
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def on_stack(name: str, op_name: str) -> bool:
    """``name`` as a whole identifier in some segment of the stack (the
    first scope inside a transform is wrapped: ``vmap(decode)``)."""
    return any(
        re.search(rf"(?<![A-Za-z0-9_]){name}(?![A-Za-z0-9_])", seg)
        for seg in op_name.split("/")
    )


def under(names: set, *scopes: str) -> set:
    return {n for n in names if all(on_stack(s, n) for s in scopes)}


def _state(model):
    return make_train_state(
        model, np.zeros((B, H, W, C), np.uint8), optimizer=optax.adamw(1e-3)
    )


def _streamformer(attn_backend="auto"):
    # two heads of 64: one 128-lane block of the fused kernel
    model = StreamFormer(patch=16, dim=128, depth=1, num_heads=2,
                         num_outputs=16, attn_backend=attn_backend)

    def loss_fn(state, params, batch):
        pred = state.apply_fn({"params": params}, batch["image"])
        return corner_loss(
            pred.reshape(-1, 8, 2), batch["xy"],
            image_shape=batch["image"].shape[1:3],
        )

    return model, loss_fn


def _lower_fused_tile(step, state):
    """The fused tile step lowered for one palettised 16x32-tile chunk
    group, as the pipeline's host stage would hand it over."""
    th, tw, cap, bits = 16, 32, 4, 4
    buf, spec = T.pack_fields({
        "image" + T.TILEIDX_SUFFIX: np.zeros((B, cap), np.int32),
        "image" + T.TILEPAL_SUFFIXES[bits]: np.zeros(
            (B, cap, th * tw * bits // 8), np.uint8
        ),
        "image" + T.PALETTE_SUFFIX: np.zeros((B, 1 << bits, C), np.uint8),
        "xy": np.zeros((B, 8, 2), np.float32),
    })
    geoms = (tuple(T.tileshape_wire(H, W, C, (th, tw))),)
    n = (H // th) * (W // tw)
    return step.jits["tile"].lower(
        state,
        jax.ShapeDtypeStruct((CHUNK, buf.shape[0]), jnp.uint8),
        {"image": jax.ShapeDtypeStruct((n, th, tw, C), jnp.uint8)},
        spec, ("image",), geoms, (),
    )


@pytest.mark.parametrize("which", ["cnn", "streamformer", "flash"])
def test_fused_tile_step_names_its_parts(which):
    """``flash``: the fused attention core's backward is a custom_vjp's,
    traced apart from its forward, and still reads under ``attn_core``
    inside ``transpose(jvp(<Model>))``."""
    model, loss_fn = (
        (CubeRegressor(features=(4,)), None) if which == "cnn"
        else _streamformer("flash" if which == "flash" else "auto")
    )
    step = make_fused_tile_step(loss_fn=loss_fn)
    state = _state(model)
    counters = reg.report()["counters"]
    names = op_names(_lower_fused_tile(step, state).compile())
    cls = type(model).__name__
    assert under(names, SCOPE_DECODE)
    # the 4-bit expansion is a lane product and selects inside ``decode``,
    # with no gather, and its trace says which form it took, once
    expand = under(names, SCOPE_DECODE, SCOPE_PALETTE_EXPAND)
    primitives = {n.rsplit("/", 1)[1] for n in expand}
    assert {"dot_general", "select_n"} <= primitives, primitives
    assert "gather" not in primitives
    took = {
        path: reg.report()["counters"].get(f"tiles.expand_path.{path}", 0)
        - counters.get(f"tiles.expand_path.{path}", 0)
        for path in T.EXPAND_PATHS
    }
    assert took == {"select": 1, "gather": 0}
    assert under(names, SCOPE_OPTIMIZER)
    # the parts do not overlap: decode runs before the scan, the
    # optimizer outside the differentiation
    assert not under(names, SCOPE_DECODE, SCOPE_OPTIMIZER)
    assert not any(
        "jvp(" in n for n in under(names, SCOPE_OPTIMIZER)
        | under(names, SCOPE_DECODE)
    )
    # forward and backward need no scope of ours
    assert any(f"/jvp({cls})/" in n for n in names)
    assert any(f"/transpose(jvp({cls}))/" in n for n in names)
    if which != "cnn":
        core = under(names, SCOPE_ATTN_CORE)
        kernels = {KERNEL_FLASH_FWD, KERNEL_FLASH_BWD}
        assert {
            k for k in kernels if under(core, k)
        } == (kernels if which == "flash" else set())
        assert all(
            "transpose(jvp(" in n for n in under(core, KERNEL_FLASH_BWD)
        )
        assert any("transpose(jvp(" in n for n in core)
        assert any(
            "jvp(" in n and "transpose(" not in n for n in core
        )
    else:
        assert not under(names, SCOPE_ATTN_CORE)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_patch_embedding_is_a_product_under_its_name(direction):
    """The input side of the StreamFormer is one name in a trace,
    ``patch_embed`` (the flax module's): the fused step holds no
    ``conv_general_dilated``, the embedding's product and the kernel's
    gradient are ``dot_general``s under that name, forward and backward."""
    model, loss_fn = _streamformer("xla")
    step = make_fused_tile_step(loss_fn=loss_fn)
    names = op_names(_lower_fused_tile(step, _state(model)).compile())
    assert not [n for n in names if n.endswith("/conv_general_dilated")]
    backward = direction == "backward"
    embed = {
        n for n in under(names, SCOPE_PATCH_EMBED)
        if "jvp(StreamFormer)" in n and ("transpose(jvp(" in n) == backward
    }
    primitives = {n.rsplit("/", 1)[1] for n in embed}
    assert "dot_general" in primitives, primitives


@pytest.mark.parametrize(
    "builder", ["per_batch", "per_batch_accum", "chunked", "echo"]
)
def test_every_step_builder_names_its_optimizer(builder):
    state = _state(CubeRegressor(features=(4,)))
    batch = {
        "image": jax.ShapeDtypeStruct((B, H, W, C), jnp.uint8),
        "xy": jax.ShapeDtypeStruct((B, 8, 2), jnp.float32),
    }
    if builder == "chunked":
        step = make_chunked_supervised_step()
        lowered = step.lower(state, {
            k: jax.ShapeDtypeStruct((CHUNK, *v.shape), v.dtype)
            for k, v in batch.items()
        })
    elif builder == "echo":
        step = make_echo_fused_step(
            lambda buffers, idx, counter: {
                k: v[idx] for k, v in buffers.items()
            }
        )
        lowered = step.jits["echo"].lower(
            state,
            {k: jax.ShapeDtypeStruct((4 * B, *v.shape[1:]), v.dtype)
             for k, v in batch.items()},
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
    else:
        step = make_supervised_step(
            accum_steps=2 if builder == "per_batch_accum" else 1
        )
        lowered = step.lower(state, batch)
    names = op_names(lowered.compile())
    opt = under(names, SCOPE_OPTIMIZER)
    assert opt and not any("jvp(" in n for n in opt)


@pytest.mark.parametrize(
    "tile, kernel",
    [((16, 32), KERNEL_TILE_DECODE_SPATIAL), (16, KERNEL_TILE_DECODE_SCATTER)],
    ids=["spatial", "scatter"],
)
def test_pallas_decode_kernels_carry_their_names(monkeypatch, tile, kernel):
    """``name=`` of the ``pallas_call`` in the jaxpr, and the kernel the
    other geometry would take is not there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    th, tw = T.tile_hw(tile)
    n = (H // th) * (W // tw)
    jaxpr = jax.make_jaxpr(
        lambda r, i, t: T.decode_tile_delta(r, i, t, (H, W, C))
    )(
        np.zeros((n, th, tw, C), np.uint8),
        np.zeros((B, 3), np.int32),
        np.zeros((B, 3, th, tw, C), np.uint8),
    )

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    said = str(call.params.get("name_and_src_info", call.params.get("name")))
    assert said.split(" ")[0] == kernel
    (other,) = {
        KERNEL_TILE_DECODE_SPATIAL, KERNEL_TILE_DECODE_SCATTER
    } - {kernel}
    assert other not in str(jaxpr)


def _driver(inflight=2):
    """A real driver over a jitted step that only counts."""
    step = jax.jit(lambda s, b: (s + 1, {"loss": b["x"].sum() + s}))
    return TrainDriver(step, jnp.zeros(()), inflight=inflight, sync_every=0)


def test_drain_books_one_drain_wait_per_blocking_drain():
    reg.reset()
    driver = _driver()
    for _ in range(2):
        driver.submit({"x": np.ones((2,), np.float32)})
    assert driver.drain() is not None
    spans = reg.report()["spans"]
    assert spans["driver.drain_wait"]["count"] == 1
    driver.submit({"x": np.ones((2,), np.float32)})
    driver.drain()
    assert reg.report()["spans"]["driver.drain_wait"]["count"] == 2


def test_drain_with_nothing_pending_books_no_wait():
    reg.reset()
    driver = _driver()
    assert driver.drain() is None
    driver.submit({"x": np.ones((2,), np.float32)})
    last = driver.drain()
    assert driver.drain() == last  # nothing in flight: the kept value
    assert reg.report()["spans"]["driver.drain_wait"]["count"] == 1


def test_mesh_driver_inherits_the_drain():
    assert MeshTrainDriver.drain is TrainDriver.drain


def test_the_vocabulary_is_documented():
    """BJX123 reads dotted metric names; the scope and kernel names have
    no dot, so their place in docs/observability.md is held here."""
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    for name in (*STEP_SCOPES, *KERNEL_NAMES):
        assert f"`{name}`" in doc, name
    assert "`driver.drain_wait`" in doc


def test_spans_lie_on_the_profilers_clock(tmp_path):
    """A span opened while the profiler runs is an event of the same
    name on a host line of the trace (CPU backend: the host plane is
    all there is)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with reg.span("driver.ring_wait"):
        jnp.ones((4,)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (found,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [
        e for plane in ProfileData.from_file(str(found)).planes
        for line in plane.lines for e in line.events
        if e.name == "driver.ring_wait"
    ]
    assert len(events) == 1 and events[0].duration_ns > 0


def test_a_process_without_jax_opens_no_annotation():
    """Producer processes never import jax: a span there must not."""
    code = (
        "import sys; import blendjax.utils.metrics as m\n"
        "with m.metrics.span('producer.frame'): pass\n"
        "assert m._TraceAnnotation is None and 'jax' not in sys.modules\n"
        "assert m.metrics.report()['spans']['producer.frame']['count'] == 1\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], check=True, cwd=ROOT, timeout=120
    )
