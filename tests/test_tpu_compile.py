"""What the chip's compiler says, asked without the chip.

libtpu compiles for a *described* TPU v5e (``on-chip-measurement`` guide
§2.3), so every Pallas kernel of the main path and the jitted steps
around them are lowered here at the widths ``chip_smoke.py`` streams
(``REAL``: 480x640x4 frames, 16x32 and 16x16 tiles) and must come back
from the TPU compiler with the kernel inside. Nothing runs: this guards against a kernel the
chip refuses (interpret mode accepts far more), a program that does not
fit 16 GB, and a mesh step that lost its collective — not against a
wrong result, which only ``chip_smoke.py`` on the chip can show.

Code under test picks its TPU branch from ``jax.default_backend()``,
which still says "cpu" here, so the ``tpu_branches`` fixture answers
"tpu" for the duration of a test. The default cases take seconds each;
the ``slow`` ones are the rest of the rehearsal to make before a chip
call (``python -m pytest -m slow tests/test_tpu_compile.py``): the
StreamFormer's fused step, the four-chip fused step, the echo-fused step
and the full-frame palette group.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import chip_smoke
from blendjax.models import CubeRegressor, StreamFormer
from blendjax.ops import tiles as T
from blendjax.train import (
    make_echo_fused_step,
    make_fused_tile_step,
    make_train_state,
)
from blendjax.train.mesh_driver import (
    make_mesh_fused_step,
    make_mesh_supervised_step,
)

REAL = chip_smoke.REAL
H, W, C = (*REAL.shape, 4)
B = REAL.batch
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here: nothing to ask
        pytest.skip(f"cannot describe a TPU v5e topology: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_off():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip (the next one warns
    and recompiles), so the cache is off around this file."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_branches(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract_state(model, sharding):
    """The train state's shapes, every array leaf on ``sharding`` —
    there is no device to hold a real one."""
    state = jax.eval_shape(
        lambda: make_train_state(model, np.zeros((B, H, W, C), np.uint8))
    )
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding)
        if hasattr(x, "shape") else x,
        state,
    )


def _tile_plan(tile, capacity=None, batch=B):
    """The packed layout + decode plan of one tile batch of ``batch``
    frames at ``REAL`` geometry, as the pipeline's host stage hands it to
    the fused step —
    the wire shape the cube producers ship (full-channel tiles as 4-bit
    per-frame palette indices, ``REAL.tile_pal_bits``):
    ``(row_bytes, spec, names, geoms, ref_shape)``."""
    th, tw = T.tile_hw(tile)
    cap = int(capacity or chip_smoke.TILE_CAPACITY[th, tw])
    n = (H // th) * (W // tw)
    bits = int(REAL.tile_pal_bits)
    buf, spec = T.pack_fields({
        "image" + T.TILEIDX_SUFFIX: np.zeros((batch, cap), np.int32),
        "image" + T.TILEPAL_SUFFIXES[bits]: np.zeros(
            (batch, cap, th * tw * bits // 8), np.uint8
        ),
        "image" + T.PALETTE_SUFFIX: np.zeros((batch, 1 << bits, C),
                                             np.uint8),
        "xy": np.zeros((batch, 8, 2), np.float32),
        "frameid": np.zeros((batch,), np.int64),
    })
    geoms = (tuple(T.tileshape_wire(H, W, C, (th, tw))),)
    return buf.shape[0], spec, ("image",), geoms, (n, th, tw, C)


def _assert_fits_with_kernel(compiled, kernel=True):
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    ma = compiled.memory_analysis()
    per_device = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )
    assert per_device < V5E_HBM_BYTES, per_device
    return text


@pytest.mark.parametrize(
    "tile, batch",
    [((16, 32), 8), ((16, 32), 128), (16, 8), (16, 128)],
    ids=["spatial-B8", "spatial-B128", "scatter-B8", "scatter-B128"],
)
def test_decode_kernel_compiles(topo, tpu_branches, tile, batch):
    """Direct-spatial (16x32) and slot-scatter (16x16) decode at one
    batch and at one K=16 chunk group (128 frames), K=288 tile slots."""
    one = SingleDeviceSharding(topo.devices[0])
    th, tw = T.tile_hw(tile)
    n = (H // th) * (W // tw)
    fn = jax.jit(lambda r, i, tl: T.decode_tile_delta(r, i, tl, (H, W, C)))
    compiled = fn.lower(
        _sds((n, th, tw, C), jnp.uint8, one),
        _sds((batch, 288), jnp.int32, one),
        _sds((batch, 288, th, tw, C), jnp.uint8, one),
    ).compile()
    _assert_fits_with_kernel(compiled)


def _attn_loss(backend, causal=False):
    from blendjax.ops.attention import local_attention

    def loss(q, k, v):
        out = local_attention(q, k, v, causal=causal, backend=backend)
        return jnp.sum(out.astype(jnp.float32))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def _under_attn_core(text, *ops):
    """The compiled program's lines whose operation is one of ``ops``
    (``pad``, ``slice``) and carries the ``attn_core`` scope."""
    from blendjax.utils.metrics import SCOPE_ATTN_CORE

    return [
        ln.strip()[:200] for ln in text.splitlines()
        if SCOPE_ATTN_CORE in ln and any(f" {op}(" in ln for op in ops)
    ]


def _assert_only_the_kept_lse_is_sliced(text, lse):
    """No pad under ``attn_core`` and one slice, the kept log-sum-exp's
    column a head (``f32[lse]``) out of the forward kernel's lane-wide
    one: no operand is padded or sliced around the kernels."""
    assert not _under_attn_core(text, "pad")
    slices = _under_attn_core(text, "slice")
    assert len(slices) == 1 and f" = f32[{lse}]{{" in slices[0], slices


@pytest.mark.parametrize(
    "shape, causal, dtype, hbm_pads",
    [
        ((4, 3072, 4, 128), False, jnp.bfloat16, False),
        ((8, 768, 4, 128), False, jnp.bfloat16, False),
        ((2, 1200, 2, 64), True, jnp.bfloat16, False),
        ((2, 1200, 12, 64), False, jnp.bfloat16, False),
        ((2, 1200, 12, 64), False, jnp.float32, False),
        ((2, 197, 12, 64), False, jnp.bfloat16, True),
        ((8, 1200, 32, 128), True, jnp.bfloat16, False),
        pytest.param((1, 16384, 4, 128), False, jnp.bfloat16, False,
                     marks=pytest.mark.slow),
    ],
    ids=["T3072", "T768", "T1200-causal", "T1200",
                           "T1200-f32", "T197-unaligned",
                           "T1200-causal-32x128", "T16384"],
)
def test_flash_attention_fwd_bwd_compiles(
    topo, tpu_branches, shape, causal, dtype, hbm_pads
):
    """The fused kernel under the blocks ``flash_block_sizes`` computes
    from the shape, at ``chip_smoke.py``'s flash shape, the StreamFormer's
    768 tokens, 1,200 at their own length (K/V padded in VMEM; causal,
    and in f32), 197 tokens no sublane tile divides (Q padded in HBM,
    K/V copied by part of a tile), the hybrid cell's 32 causal heads of
    128 (K and V broadcast from 2), and the most keys it admits."""
    q = _sds(shape, dtype, SingleDeviceSharding(topo.devices[0]))
    compiled = _attn_loss("flash", causal).lower(q, q, q).compile()
    text = _assert_fits_with_kernel(compiled)
    assert bool(_under_attn_core(text, "pad")) == hbm_pads


@pytest.mark.parametrize(
    "shape, causal, dtype",
    [
        ((8, 1200, 12, 64), False, jnp.bfloat16),
        ((2, 1200, 12, 64), True, jnp.float32),
        ((8, 768, 4, 128), False, jnp.bfloat16),
        ((4, 3072, 4, 128), False, jnp.bfloat16),
    ],
    ids=["T1200", "T1200-causal-f32", "T768", "T3072-six-blocks"],
)
def test_packed_attention_fwd_bwd_compiles(
    topo, tpu_branches, shape, causal, dtype
):
    """The kernels on the packed ``(B, T, 3·H·D)`` product: q, k and v
    read by column block, the backward's stores at a lane offset the
    head block decides, a batch row of the packed gradient in VMEM
    (22 MB with its write-back at 1,200 tokens of 768 in f32) — at the
    benchmark's shape, ``chip_smoke.py``'s 768 tokens, and six query
    blocks whose dq rows land one block at a time."""
    from blendjax.ops.attention import (
        attention_reads_packed,
        local_attention_packed,
    )

    b, t, h, d = shape
    assert attention_reads_packed(b, t, h, d, dtype, "flash")
    one = SingleDeviceSharding(topo.devices[0])

    def loss(qkv, bias):
        out = local_attention_packed(qkv, h, bias=bias, causal=causal,
                                     backend="flash")
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        _sds((b, t, 3 * h * d), dtype, one), _sds((3 * h * d,), dtype, one)
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    assert not _under_attn_core(text, "pad", "slice", "copy", "transpose")


def test_auto_attention_at_the_benchmark_shape_is_fused(
    topo, tpu_branches, monkeypatch
):
    """(8, 1200, 12, 64) bf16 through ``auto`` on a one-chip machine:
    both kernels inside by name, the backward's under the ``attn_core``
    scope too, no pad and no slice of an operand around them (1,200
    tokens run as 1,200) and less temporary memory than the materialised
    path."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    from blendjax.utils.metrics import (
        KERNEL_FLASH_BWD,
        KERNEL_FLASH_FWD,
        SCOPE_ATTN_CORE,
    )

    q = _sds((8, 1200, 12, 64), jnp.bfloat16,
             SingleDeviceSharding(topo.devices[0]))
    auto = _attn_loss("auto").lower(q, q, q).compile()
    text = _assert_fits_with_kernel(auto)
    op_names = {
        ln.split("=")[0].strip(): ln.split('op_name="')[1].split('"')[0]
        for ln in text.splitlines() if "tpu_custom_call" in ln
    }
    fwd, bwd = (
        next(op for call, op in op_names.items()
             if call.startswith(f"%{kernel}"))
        for kernel in (KERNEL_FLASH_FWD, KERNEL_FLASH_BWD)
    )
    assert SCOPE_ATTN_CORE in fwd and "transpose(" not in fwd, fwd
    assert SCOPE_ATTN_CORE in bwd and "transpose(jvp(" in bwd, bwd
    _assert_only_the_kept_lse_is_sliced(text, "8,1200,12")
    xla = _attn_loss("xla").lower(q, q, q).compile()
    _assert_fits_with_kernel(xla, kernel=False)
    assert (
        auto.memory_analysis().temp_size_in_bytes
        < xla.memory_analysis().temp_size_in_bytes / 2
    )


def _hbm_lines(text, *shapes):
    """The compiled program's instructions (not the lines inside a
    fusion's computation, which live in registers) whose result has one
    of ``shapes`` (``f32[8,1200,64`` ...)."""
    entry = text[text.index("ENTRY "):]
    return [
        ln.strip()[:200] for ln in entry.splitlines()
        if " = " in ln and any(
            ln.split(" = ", 1)[1].lstrip("(").startswith(s) for s in shapes
        )
    ]


def test_ssd_scan_fwd_bwd_compiles(topo, tpu_branches, monkeypatch):
    """The state-space scan's kernel pair at ``nemotron3nano_replay``'s
    layer shape (8 x 1,200 tokens, 64 heads of 64 in 8 groups, state
    128, chunk 128: a ragged last chunk of 48) through ``auto`` on a
    one-chip machine: both kernels come back from the TPU compiler by
    name under SSD_VMEM_BYTES, the backward's under the ``ssd`` scope
    too, and nothing of a chunk's ``L x L`` size or of a padded length
    is in HBM."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    from blendjax.ops.ssd import ssd_chunked
    from blendjax.utils.metrics import (
        KERNEL_SSD_BWD,
        KERNEL_SSD_FWD,
        SCOPE_SSD,
    )

    one = SingleDeviceSharding(topo.devices[0])
    b, t, h, p, g, n = 8, 1200, 64, 64, 8, 128

    def loss(*v):
        return jnp.sum(ssd_chunked(*v, chunk=128).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, tuple(range(6)))).lower(
        _sds((b, t, h, p), jnp.bfloat16, one), _sds((b, t, h), jnp.float32, one),
        _sds((h,), jnp.float32, one), _sds((b, t, g, n), jnp.bfloat16, one),
        _sds((b, t, g, n), jnp.bfloat16, one), _sds((h,), jnp.float32, one),
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    op_names = {
        ln.split("=")[0].strip(): ln.split('op_name="')[1].split('"')[0]
        for ln in text.splitlines() if "tpu_custom_call" in ln
    }
    fwd, bwd = (
        next(op for call, op in op_names.items()
             if call.startswith(f"%{kernel}"))
        for kernel in (KERNEL_SSD_FWD, KERNEL_SSD_BWD)
    )
    assert SCOPE_SSD in fwd and "transpose(" not in fwd, fwd
    assert SCOPE_SSD in bwd and "transpose(jvp(" in bwd, bwd
    assert not _hbm_lines(text, "f32[8,10,8,8,128,128", "bf16[8,10,8,8,128,128",
                          "bf16[8,1280", "f32[8,1280")


def _nemotron_kwargs(**over):
    """``nemotron3_nano_30b_a3b``'s model arguments, as the benchmark's
    configuration file has them, some replaced."""
    import json

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "nemotron3_nano_30b_a3b.json",
    )) as f:
        return {**json.load(f)["model"]["kwargs"], **over}


@pytest.mark.slow  # 5 to 18 s of the TPU compiler on every core, each
@pytest.mark.parametrize("kind, must_hold", [
    ("M", ("ssd_scan_fwd", "ssd_scan_bwd")),
    ("E", ()),
    ("*", ("flash_attention_fwd", "flash_attention_bwd")),
], ids=["mamba2", "experts", "gqa"])
def test_hybrid_layer_compiles_at_published_widths(
    topo, tpu_branches, monkeypatch, kind, must_hold
):
    """One layer of each kind of ``nemotron3_nano_30b_a3b`` (the
    benchmark's configuration file's own arguments), forward and
    backward over 8 x 1,200 tokens of width 2688: the chunked scan
    through its kernel pair (no chunk's ``L x L`` decay tensor in HBM,
    and x, B, C and y neither copied, padded, sliced nor broadcast to
    heads in front of or behind the kernels), the held experts,
    grouped-query attention through the fused kernels. The whole fused
    step is ``benchmark/compile_rehearsal.py``'s."""
    from blendjax.models import StreamHybrid

    monkeypatch.setattr(jax, "device_count", lambda: 1)  # a one-chip machine
    model = StreamHybrid(**_nemotron_kwargs(pattern=kind, remat=False))
    placed = SingleDeviceSharding(topo.devices[0])
    images = _sds((B, H, W, C), jnp.uint8, placed)
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, placed),
        jax.eval_shape(
            lambda: model.init(jax.random.key(0), np.zeros((B, H, W, C),
                                                           np.uint8))
        )["params"],
    )

    def loss(p, x):
        return jnp.sum(model.apply({"params": p}, x))

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, images
    ).compile()
    text = _assert_fits_with_kernel(
        compiled, kernel=bool(must_hold)
    )
    for name in must_hold:
        assert name in text, name
    if kind == "M":
        assert not _hbm_lines(text, "f32[8,10,8,8,128,128",
                              "bf16[8,10,8,8,128,128", "bf16[8,1280",
                              "f32[8,1280", "bf16[8,1200,64,128")
        # what the kernels read is what a fusion of the mixer wrote: no
        # layout copy, slice or pad of an activation stands between (dt
        # alone is re-laid out, f32[8,64,1200])
        calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "%ssd_scan_" in ln]
        names = {
            name for ln in calls
            for name in ln.split("custom-call(")[1].split(")")[0].split(", ")
        }
        made_by = {
            ln.split(" = ")[0].strip(): ln.split(" = ")[1]
            for ln in text[text.index("ENTRY "):].splitlines() if " = " in ln
        }
        for name in names:
            name = name.split("*/")[-1]
            made = made_by[name]
            if made.startswith(("bf16[8,1200,4096]", "bf16[8,1200,1024]")):
                # (a `copy-done` is XLA's prefetch into faster memory,
                # the same layout, overlapped)
                assert not any(f" {op}(" in made for op in (
                    "copy", "slice", "pad", "transpose", "broadcast",
                    "concatenate",
                )), made


def test_windowed_flash_attention_compiles(topo, tpu_branches):
    """The windowed pair at ``trinitymini_replay``'s sliding layer (2 x
    4,800 tokens, 32 causal heads of 128, window 2,048): both kernels
    come back from the TPU compiler by name, and no pad or slice of an
    operand stands around them (4,800 tokens run as 4,800, K/V padded to
    4,864 in VMEM and read a 2,688-key slab a query block there)."""
    from blendjax.ops.attention import local_attention
    from blendjax.utils.metrics import (
        KERNEL_FLASH_WINDOW_BWD,
        KERNEL_FLASH_WINDOW_FWD,
    )

    def loss(q, k, v):
        return jnp.sum(local_attention(
            q, k, v, causal=True, backend="flash", window=2048
        ).astype(jnp.float32))

    q = _sds((2, 4800, 32, 128), jnp.bfloat16,
             SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        q, q, q
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    assert f"%{KERNEL_FLASH_WINDOW_FWD}" in text
    assert f"%{KERNEL_FLASH_WINDOW_BWD}" in text
    assert "%flash_attention_fwd" not in text
    _assert_only_the_kept_lse_is_sliced(text, "2,4800,32")


def _trinity_kwargs(**over):
    """``trinity_mini``'s model arguments, as the benchmark's
    configuration file has them, some replaced."""
    import json

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "trinity_mini.json",
    )) as f:
        return {**json.load(f)["model"]["kwargs"], **over}


@pytest.mark.slow  # 15 to 20 s of the TPU compiler on every core, each
@pytest.mark.parametrize("layer_types, dense, kernels", [
    ("S", 1, ("flash_attention_window_fwd", "flash_attention_window_bwd")),
    ("F", 0, ("flash_attention_fwd", "flash_attention_bwd")),
], ids=["sliding-dense", "full-experts"])
def test_moe_former_layer_compiles_at_published_widths(
    topo, tpu_branches, monkeypatch, layer_types, dense, kernels
):
    """One layer of each kind of ``trinity_mini`` (the benchmark's
    configuration file's own arguments), forward and backward over 2 x
    4,800 tokens of width 2048: a sliding layer with its dense SwiGLU
    through the windowed pair, a full layer with its gated experts
    through today's pair; both kernels of each under the ``attn_block``
    scope, the backward's too. The whole fused step is
    ``benchmark/compile_rehearsal.py``'s."""
    from blendjax.models import StreamMoEFormer
    from blendjax.utils.metrics import SCOPE_ATTN_BLOCK

    monkeypatch.setattr(jax, "device_count", lambda: 1)  # a one-chip machine
    model = StreamMoEFormer(**_trinity_kwargs(
        layer_types=layer_types, num_dense_layers=dense, remat=False
    ))
    placed = SingleDeviceSharding(topo.devices[0])
    images = _sds((2, H, W, C), jnp.uint8, placed)
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, placed),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), np.zeros((2, H, W, C), np.uint8)
        ))["params"],
    )
    compiled = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(model.apply({"params": p}, x))
    )).lower(params, images).compile()
    text = _assert_fits_with_kernel(compiled)
    for kernel in kernels:
        calls = re.findall(rf'%{kernel}[.\d]* = .*op_name="([^"]*)"', text)
        assert calls and all(SCOPE_ATTN_BLOCK in c for c in calls), kernel


def _outside_fusions(text):
    """The compiled program's instructions outside fused computations:
    one line an operation as the chip runs it (a fusion's inner
    operations carry the same name stack)."""
    fused = set(re.findall(r" fusion\(.*calls=(%[\w.-]+)", text))
    inside = False
    for ln in text.splitlines():
        if ln and not ln[0].isspace() and ln.endswith("{"):
            inside = ln.split(" ")[0] in fused
        elif not inside:
            yield ln


def test_fused_hybrid_step_recomputes_no_large_product(
    topo, tpu_branches, monkeypatch
):
    """The fused tile step of one Mamba-2 and one expert layer of
    ``nemotron3_nano_30b_a3b`` at published widths under ``remat`` (a
    chunk of 2 updates: the scan's body is the same at any): the held
    experts' up-product ``[9600,8,1856]``, the shared expert's
    ``[9600,3712]`` and the input projection ``[8,1200,10304]`` are made
    once an update, in the forward, and the scan's forward kernel runs
    once; nothing of them is under the recomputed forward
    (``rematted_computation`` in the name stack), where the rest of the
    layers is. ``benchmark/compile_rehearsal.py`` sizes the whole step
    so: 12.56 GB a chip with these residuals kept, 9.40 with plain
    ``remat``, 17.05 without."""
    from blendjax.models import StreamHybrid
    from blendjax.utils.metrics import KERNEL_SSD_FWD, metrics

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    one = SingleDeviceSharding(topo.devices[0])
    model = StreamHybrid(**_nemotron_kwargs(pattern="ME", remat=True))
    step = make_fused_tile_step(loss_fn=chip_smoke.former_loss)
    state = _abstract_state(model, one)
    before = metrics.report()["counters"].get("remat.saved_residuals", 0)
    lowered = _lower_fused_tile(step, state, 2, one, _tile_plan((16, 32)))
    # a trace names 5: the expert layer's 2, the Mamba-2 layer's input
    # projection and the scan kernel's output and states
    assert metrics.report()["counters"]["remat.saved_residuals"] == before + 5
    text = _assert_fits_with_kernel(lowered.compile())
    products = {}
    for ln in _outside_fusions(text):
        made = re.match(
            r"\s*%\S+ = [a-z0-9]+\[(9600,8,1856|9600,3712|8,1200,10304)\]",
            ln,
        )
        name = re.search(r'op_name="([^"]*)"', ln)
        if made and name and name.group(1).endswith((
            "moe_experts/nc,ecf->nef/dot_general",
            "moe_shared/shared_up/dot_general", "in_proj/dot_general",
        )):
            products.setdefault(made.group(1), []).append(name.group(1))
    assert sorted(products) == ["8,1200,10304", "9600,3712", "9600,8,1856"]
    for names in products.values():
        assert len(names) == 1 and "transpose(" not in names[0], names
    kernels = re.findall(
        rf'%{KERNEL_SSD_FWD}[.\d]* = .*op_name="([^"]*)"', text
    )
    assert len(kernels) == 1 and "rematted_computation" not in kernels[0]
    assert "rematted_computation" in text  # the rest is recomputed


def test_fused_moe_former_step_recomputes_no_large_product(
    topo, tpu_branches, monkeypatch
):
    """The fused tile step of two layers of ``trinity_mini`` at published
    widths under its ``remat`` (``S F``, the first dense; 2 x 4,800
    tokens, a chunk of 2 updates): each layer's forward kernel
    (``flash_attention_window_fwd``, ``flash_attention_fwd``) runs once,
    outside the recomputed forward; the q, k, v, gate and ``proj``
    products and the dense layer's up, gate and down products are made
    once an update, in the forward; and of the layers' products only the
    expert layer's router and its experts' and shared expert's
    down-products (whose output is not kept) are under
    ``rematted_computation``, where the norms, RoPE, the K/V repeat and
    the activations are. ``benchmark/compile_rehearsal.py`` sizes the
    whole step (6 layers) so: 14.65 GB a chip with these kept, 12.50
    with the experts' products alone kept."""
    from blendjax.models import StreamMoEFormer
    from blendjax.utils.metrics import (
        KERNEL_FLASH_FWD,
        KERNEL_FLASH_WINDOW_FWD,
        metrics,
    )

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    one = SingleDeviceSharding(topo.devices[0])
    model = StreamMoEFormer(**_trinity_kwargs(
        layer_types="SF", num_dense_layers=1, remat=True
    ))
    step = make_fused_tile_step(loss_fn=chip_smoke.former_loss)
    state = _abstract_state(model, one)
    before = metrics.report()["counters"].get("remat.saved_residuals", 0)
    lowered = _lower_fused_tile(step, state, 2, one,
                                _tile_plan((16, 32), batch=2))
    # a trace names 23: each layer's q, k, v and gate, gated output,
    # attention output and its kernel's output and log-sum-exp (2 x 8),
    # the dense layer's up, gate and output, the expert layer's experts
    # and shared expert, up and gate
    assert metrics.report()["counters"]["remat.saved_residuals"] == (
        before + 23
    )
    text = _assert_fits_with_kernel(lowered.compile())
    for kernel in (KERNEL_FLASH_WINDOW_FWD, KERNEL_FLASH_FWD):
        calls = re.findall(rf'%{kernel}[.\d]* = .*op_name="([^"]*)"', text)
        assert len(calls) == 1, (kernel, calls)
        assert "rematted_computation" not in calls[0], kernel
    products = [
        re.search(r'op_name="([^"]*)"', ln).group(1)
        for ln in text.splitlines()
        if re.search(r" (dot|convolution)\(", ln) and "op_name=" in ln
    ]
    grouped = ("attn._grouped/q", "attn._grouped/k", "attn._grouped/v",
               "attn._grouped/gate", "attn._grouped/proj")
    for layer, made_here in ((0, grouped + ("ffn/up", "ffn/gate",
                                            "ffn/down")),
                             (1, grouped)):
        for product in made_here:
            made = [
                name for name in products if f"/layer{layer}/" in name
                and name.endswith(f"/{product}/dot_general")
                and "transpose(" not in name
            ]
            assert len(made) == 1, (layer, product, made)
    recomputed = [n for n in products if "rematted_computation" in n]
    assert recomputed and all(n.endswith(tuple(
        f"/layer1/ffn/moe/{part}/dot_general" for part in (
            "moe_route", "moe_experts/nef,efc->nc", "moe_shared/shared_down"
        )
    )) for n in recomputed), recomputed


def test_delta_rule_fwd_bwd_compiles(topo, tpu_branches, monkeypatch):
    """The gated delta rule's kernel pair at ``olmohybrid_replay``'s layer
    shape (2 x 4,800 tokens, 15 heads of 96 / 192, chunks of 64) through
    ``auto`` on a one-chip machine (which takes it: the working set is
    within DELTA_VMEM_BYTES, v5e's default scoped VMEM): both kernels come
    back from the TPU compiler by name, both under the ``delta_rule``
    scope, the backward's too; no
    ``triangular-solve`` is left, and nothing chunk-major (N, B, H, C, ·)
    is in HBM: of a chunk's (C, C) size a head only the forward's
    ``(I + A)^-1``, which the backward reads."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    from blendjax.ops.delta_rule import gated_delta_rule_chunked
    from blendjax.utils.metrics import (
        KERNEL_DELTA_RULE_BWD,
        KERNEL_DELTA_RULE_FWD,
        SCOPE_DELTA_RULE,
    )

    one = SingleDeviceSharding(topo.devices[0])
    b, t, h, dk, dv = 2, 4800, 15, 96, 192

    def loss(*v):
        o, _ = gated_delta_rule_chunked(*v, chunk=64)
        return jnp.sum(o)

    compiled = jax.jit(jax.value_and_grad(loss, tuple(range(5)))).lower(
        _sds((b, t, h, dk), jnp.bfloat16, one),
        _sds((b, t, h, dk), jnp.bfloat16, one),
        _sds((b, t, h, dv), jnp.bfloat16, one),
        _sds((b, t, h), jnp.float32, one), _sds((b, t, h), jnp.float32, one),
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    op_names = {
        ln.split("=")[0].strip(): ln.split('op_name="')[1].split('"')[0]
        for ln in text.splitlines() if "tpu_custom_call" in ln
    }
    fwd, bwd = (
        [op for call, op in op_names.items() if call.startswith(f"%{kernel}")]
        for kernel in (KERNEL_DELTA_RULE_FWD, KERNEL_DELTA_RULE_BWD)
    )
    assert len(fwd) == len(bwd) == 1, op_names
    assert SCOPE_DELTA_RULE in fwd[0] and "transpose(" not in fwd[0], fwd
    assert SCOPE_DELTA_RULE in bwd[0] and "transpose(jvp(" in bwd[0], bwd
    assert "triangular-solve" not in text
    assert not _hbm_lines(text, "f32[75,2,15,64", "bf16[75,2,15,64",
                          "f32[2,15,75,64")
    kept = _hbm_lines(text, "f32[2,75,15,64,64")
    assert kept and all(
        f"get-tuple-element(%{KERNEL_DELTA_RULE_FWD}" in ln for ln in kept
    ), kept


def _olmo_kwargs(**over):
    """``olmo_hybrid_7b``'s model arguments, as the benchmark's
    configuration file has them, some replaced."""
    import json

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "olmo_hybrid_7b.json",
    )) as f:
        return {**json.load(f)["model"]["kwargs"], **over}


def test_fused_delta_hybrid_step_runs_the_kernel_pair(
    topo, tpu_branches, monkeypatch
):
    """The fused tile step of one gated delta-rule layer of
    ``olmo_hybrid_7b`` at published widths under its ``remat`` (2 x 4,800
    tokens, a chunk of 2 updates: the scan's body is the same at any):
    the recurrence is the kernel pair, the forward kernel once, outside the
    recomputed forward (its chunk states are kept, ``gdn_states``), the
    backward once, both under ``delta_rule``, and no ``triangular-solve``
    is left anywhere. ``benchmark/compile_rehearsal.py`` sizes the whole
    step (4 layers)."""
    from blendjax.models import StreamDeltaHybrid
    from blendjax.utils.metrics import (
        KERNEL_DELTA_RULE_BWD,
        KERNEL_DELTA_RULE_FWD,
        SCOPE_DELTA_RULE,
    )

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    one = SingleDeviceSharding(topo.devices[0])
    model = StreamDeltaHybrid(**_olmo_kwargs(layer_types="L"))
    step = make_fused_tile_step(loss_fn=chip_smoke.former_loss)
    lowered = _lower_fused_tile(step, _abstract_state(model, one), 2, one,
                                _tile_plan((16, 32), batch=2))
    text = _assert_fits_with_kernel(lowered.compile())
    for kernel, under in ((KERNEL_DELTA_RULE_FWD, "jvp("),
                          (KERNEL_DELTA_RULE_BWD, "transpose(jvp(")):
        calls = re.findall(rf'%{kernel}[.\d]* = .*op_name="([^"]*)"', text)
        assert len(calls) == 1, (kernel, calls)
        assert SCOPE_DELTA_RULE in calls[0] and under in calls[0], calls
        assert "rematted_computation" not in calls[0], calls
    assert "rematted_computation" in text  # the rest is recomputed
    assert "triangular-solve" not in text


def test_gamma_normalize_compiles(topo):
    """The Pallas gamma kernel is gone (the chip refused its uint8 ->
    float32 cast and nothing called it); what replaced it is plain jnp
    and the chip takes it."""
    from blendjax.ops.image import uint8_gamma_normalize

    one = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(uint8_gamma_normalize).lower(
        _sds((B, H, W, C), jnp.uint8, one)
    ).compile()
    _assert_fits_with_kernel(compiled, kernel=False)


def _assert_no_gather_in_palette_expand(text):
    """The chip's compiler kept the 4-bit expansion dense: operations
    traced under ``palette_expand``, fused ones included, and no gather
    among them (it ran one index at a time: 49 ms a dispatch)."""
    from blendjax.utils.metrics import SCOPE_PALETTE_EXPAND

    ops = [
        ln for ln in text.splitlines()
        if SCOPE_PALETTE_EXPAND in ln.partition("op_name=")[2]
    ]
    assert ops and not [ln for ln in ops if " gather(" in ln]


def _lower_fused_tile(step, state, chunk, sharding, plan):
    row_bytes, spec, names, geoms, ref_shape = plan
    return step.jits["tile"].lower(
        state,
        _sds((chunk, row_bytes), jnp.uint8, sharding),
        {"image": _sds(ref_shape, jnp.uint8, sharding)},
        spec, names, geoms, (),
    )


@pytest.mark.parametrize(
    "model_and_loss",
    [
        lambda: (CubeRegressor(), None),
        pytest.param(
            lambda: (StreamFormer(**REAL.former), chip_smoke.former_loss),
            marks=pytest.mark.slow,
        ),
    ],
    ids=["cnn", "streamformer"],
)
def test_fused_tile_step_compiles_with_the_kernel(
    topo, tpu_branches, model_and_loss
):
    """``make_fused_tile_step`` whole at ``REAL``'s K=16 — unpack,
    palette expand, Pallas decode, 16 scanned updates — with the kernel
    branch actually taken and the expansion without a gather."""
    one = SingleDeviceSharding(topo.devices[0])
    model, loss_fn = model_and_loss()
    step = make_fused_tile_step(loss_fn=loss_fn)
    compiled = _lower_fused_tile(
        step, _abstract_state(model, one), REAL.chunk, one,
        _tile_plan((16, 32)),
    ).compile()
    _assert_no_gather_in_palette_expand(_assert_fits_with_kernel(compiled))


def _assert_embedding_is_a_product(text):
    """In the step compiled for the described v5e: nothing traced as a
    ``conv_general_dilated``, the embedding's product and the kernel's
    gradient are ``dot_general``s under ``patch_embed``, and no operation
    of the scan's body copies one update's u8 frames into another layout
    (the convolution asked for them batch-minor: ``%copy.894``, 0.47 ms
    an update on the chip; the product reads the scan's slice through the
    fusion that scales it)."""
    import re

    from blendjax.utils.metrics import SCOPE_PATCH_EMBED

    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert not [n for n in op_names if n.endswith("/conv_general_dilated")]
    products = [
        n for n in op_names
        if SCOPE_PATCH_EMBED in n.split("/") and n.endswith("/dot_general")
    ]
    assert [n for n in products if "transpose(jvp(" not in n]
    assert [n for n in products if "transpose(jvp(" in n]
    frame_copies = [
        ln.strip()[:160] for ln in text.splitlines()
        if re.search(rf" = u8\[1,\d+,{H},{W},{C}\]\S* copy\(", ln)
    ]
    assert not frame_copies, frame_copies


def _vit_stem_former():
    """``vit_b16``'s input side and one of its blocks: the embedding's
    program does not depend on the depth, and one block compiles in
    seconds."""
    return StreamFormer(patch=16, dim=768, depth=1, num_heads=12,
                        num_outputs=16)


def test_fused_step_embeds_patches_by_a_product(topo, tpu_branches):
    """One chip, ``REAL`` frames, the benchmark's patch 16 and width 768:
    a small chunk, since the scan's body is the same at any."""
    one = SingleDeviceSharding(topo.devices[0])
    step = make_fused_tile_step(loss_fn=chip_smoke.former_loss)
    compiled = _lower_fused_tile(
        step, _abstract_state(_vit_stem_former(), one), 2, one,
        _tile_plan((16, 32)),
    ).compile()
    _assert_embedding_is_a_product(_assert_fits_with_kernel(compiled))


def _assert_attention_reads_the_packed_product(text, batch):
    """In the step compiled for the described v5e the fused kernels sit
    directly on the ``qkv`` product and its transposes: both are inside
    by name under ``attn_core``, the backward's first result is the
    one packed gradient (the bias's column sums beside it), and no ``copy``, ``transpose``, ``concatenate`` or
    ``dynamic-update-slice`` of one q, k or v's size (``batch`` images a
    device x 1,200 tokens x 768) or more carries the ``attn_core``
    scope or the ``qkv`` module's name (six layout copies a layer stood
    there: 1.38 ms an update on the chip)."""
    import re

    from blendjax.utils.metrics import (
        KERNEL_FLASH_BWD,
        KERNEL_FLASH_FWD,
        SCOPE_ATTN_CORE,
    )

    calls = {
        kernel: [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln
                 and ln.strip().startswith(f"%{kernel}")]
        for kernel in (KERNEL_FLASH_FWD, KERNEL_FLASH_BWD)
    }
    assert all(calls.values()), {k: len(v) for k, v in calls.items()}
    for lines in calls.values():
        assert all(SCOPE_ATTN_CORE in ln for ln in lines)
    packed = f"bf16[{batch},1200,2304]{{2,1,0"
    assert all(ln.split(" = ")[1].lstrip("(").startswith(packed)
               for ln in calls[KERNEL_FLASH_BWD])
    moved = []
    for ln in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* "
            r"(copy|transpose|concatenate|dynamic-update-slice)\(", ln
        )
        name = ln.partition('op_name="')[2].partition('"')[0]
        if m and (SCOPE_ATTN_CORE in name or "/qkv/" in name):
            if np.prod([int(n) for n in m.group(1).split(",")]) >= (
                batch * 1200 * 768
            ):
                moved.append(ln.strip()[:200])
    assert not moved, moved


def test_fused_step_attention_reads_the_packed_product(
    topo, tpu_branches, monkeypatch
):
    """One chip, the benchmark's width and 1,200 tokens, one block (the
    layers are alike), a small chunk (the scan's body is the same at
    any)."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    one = SingleDeviceSharding(topo.devices[0])
    step = make_fused_tile_step(loss_fn=chip_smoke.former_loss)
    compiled = _lower_fused_tile(
        step, _abstract_state(_vit_stem_former(), one), 2, one,
        _tile_plan((16, 32)),
    ).compile()
    _assert_attention_reads_the_packed_product(
        _assert_fits_with_kernel(compiled), B
    )


def test_fused_tile_step_names_the_decode_kernel(topo, tpu_branches):
    """In the step compiled for the described v5e the Pallas decode is
    found by name, not by shape: the custom call's ``op_name`` carries
    the ``decode`` scope and the kernel's name (what a trace of the chip
    shows as the operation's ``tf_op``), and the TPU lowering names the
    instruction after the ``pallas_call``'s ``name=``. A small chunk:
    the names do not depend on it and the compile takes seconds."""
    import re

    from blendjax.utils.metrics import (
        KERNEL_TILE_DECODE_SPATIAL,
        SCOPE_DECODE,
    )

    one = SingleDeviceSharding(topo.devices[0])
    compiled = _lower_fused_tile(
        make_fused_tile_step(), _abstract_state(CubeRegressor(), one), 2,
        one, _tile_plan((16, 32)),
    ).compile()
    (call,) = [
        ln for ln in compiled.as_text().splitlines()
        if "tpu_custom_call" in ln and " custom-call(" in ln
    ]
    segments = re.search(r'op_name="([^"]*)"', call).group(1).split("/")
    assert SCOPE_DECODE in segments
    assert KERNEL_TILE_DECODE_SPATIAL in segments
    assert call.strip().startswith(f"%{KERNEL_TILE_DECODE_SPATIAL}")


@pytest.fixture
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def test_four_chip_data_parallel_step_has_its_all_reduce(topo, mesh4):
    """The question is the collective, not the width: a narrow CNN keeps
    this to seconds (the full-width mesh step is the ``slow`` fused case
    below)."""
    rep = NamedSharding(mesh4, P())
    by_batch = NamedSharding(mesh4, P("data"))
    state = _abstract_state(CubeRegressor(features=(8, 16)), rep)
    compiled = make_mesh_supervised_step(state, mesh4).lower(
        state,
        {
            "image": _sds((B, H, W, C), jnp.uint8, by_batch),
            "xy": _sds((B, 8, 2), jnp.float32, by_batch),
        },
    ).compile()
    text = _assert_fits_with_kernel(compiled, kernel=False)
    assert "all-reduce(" in text or "all-reduce-start(" in text


def test_four_chip_sharded_decode_keeps_the_kernel(topo, tpu_branches, mesh4):
    """The kernel cannot be partitioned by GSPMD; with ``mesh=`` it goes
    through ``shard_map`` over the batch axis and survives."""
    rep = NamedSharding(mesh4, P())
    by_batch = NamedSharding(mesh4, P("data"))
    th, tw = 16, 32
    cap = int(chip_smoke.TILE_CAPACITY[th, tw])
    n = (H // th) * (W // tw)
    fn = jax.jit(
        lambda r, i, tl: T.decode_tile_delta(r, i, tl, (H, W, C), mesh=mesh4)
    )
    compiled = fn.lower(
        _sds((n, th, tw, C), jnp.uint8, rep),
        _sds((B, cap), jnp.int32, by_batch),
        _sds((B, cap, th, tw, C), jnp.uint8, by_batch),
    ).compile()
    _assert_fits_with_kernel(compiled)


def test_four_chip_attention_runs_per_shard(topo, tpu_branches, mesh4):
    """Data-parallel at batch 32 with the mesh declared, as the mesh
    step builders do: the kernel survives partitioning (shard_map over
    the batch axis), q/k/v are not gathered, and the gradient of a
    replicated parameter still has its all-reduce."""
    from blendjax.ops.attention import batch_sharded_over, local_attention

    def loss(scale, q, k, v):
        with batch_sharded_over(mesh4, "data"):
            out = local_attention(q * scale, k, v)
        return jnp.sum(out.astype(jnp.float32))

    q = _sds((32, 1200, 12, 64), jnp.bfloat16, NamedSharding(mesh4, P("data")))
    scale = _sds((64,), jnp.bfloat16, NamedSharding(mesh4, P()))
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        scale, q, q, q
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    assert "all-gather" not in text
    assert "all-reduce(" in text or "all-reduce-start(" in text
    assert "bf16[8,1200,768]" in text  # one shard, its own length, heads in lanes


def test_four_chip_ssd_scan_runs_per_shard(topo, tpu_branches, mesh4):
    """The scan's kernel pair data-parallel at batch 32 with the mesh
    declared: each chip runs it over its 8 rows (``shard_map`` over the
    batch axis), nothing is gathered, and the per-head gradients of the
    replicated ``a`` and ``d`` have their all-reduce."""
    from blendjax.ops.attention import batch_sharded_over
    from blendjax.ops.ssd import ssd_chunked

    def loss(*v):
        with batch_sharded_over(mesh4, "data"):
            return jnp.sum(ssd_chunked(*v, chunk=128).astype(jnp.float32))

    rows, whole = NamedSharding(mesh4, P("data")), NamedSharding(mesh4, P())
    b, t, h, p, g, n = 32, 1200, 64, 64, 8, 128
    compiled = jax.jit(jax.value_and_grad(loss, tuple(range(6)))).lower(
        _sds((b, t, h, p), jnp.bfloat16, rows), _sds((b, t, h), jnp.float32, rows),
        _sds((h,), jnp.float32, whole), _sds((b, t, g, n), jnp.bfloat16, rows),
        _sds((b, t, g, n), jnp.bfloat16, rows), _sds((h,), jnp.float32, whole),
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    assert "%ssd_scan_fwd" in text and "%ssd_scan_bwd" in text
    assert "all-gather" not in text
    assert "all-reduce(" in text or "all-reduce-start(" in text
    assert "bf16[8,1200,4096]" in text  # one shard, its own length


def test_undeclared_attention_in_a_partitioned_program(
    topo, tpu_branches, mesh4
):
    """Several devices and no ``batch_sharded_over``: ``auto`` keeps the
    XLA path, which GSPMD partitions; an explicit ``flash`` is a bare
    custom call and the lowering refuses it — it does not quietly
    gather."""
    q = _sds((32, 1200, 12, 64), jnp.bfloat16, NamedSharding(mesh4, P("data")))
    _assert_fits_with_kernel(
        _attn_loss("auto").lower(q, q, q).compile(), kernel=False
    )
    with pytest.raises(NotImplementedError, match="shard_map"):
        _attn_loss("flash").lower(q, q, q)


@pytest.mark.slow
def test_four_chip_fused_step_has_kernel_and_all_reduce(
    topo, tpu_branches, mesh4
):
    """What ``MeshTrainDriver.build(fused=True)`` dispatches: the packed
    group arrives replicated, decodes shard-locally through the kernel,
    trains data-parallel."""
    rep = NamedSharding(mesh4, P())
    state = _abstract_state(CubeRegressor(), rep)
    step = make_mesh_fused_step(state, mesh4)
    compiled = _lower_fused_tile(
        step, state, 2, rep, _tile_plan((16, 32))
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    _assert_no_gather_in_palette_expand(text)
    assert "all-reduce(" in text or "all-reduce-start(" in text


@pytest.mark.slow
def test_four_chip_fused_step_embeds_patches_by_a_product(
    topo, tpu_branches, mesh4
):
    """The same on the 2x2 data mesh (each chip cuts its own two
    frames): GSPMD partitions the product over the batch, and no shard
    copies its frames either."""
    rep = NamedSharding(mesh4, P())
    state = _abstract_state(_vit_stem_former(), rep)
    step = make_mesh_fused_step(
        state, mesh4, loss_fn=chip_smoke.former_loss
    )
    compiled = _lower_fused_tile(
        step, state, 2, rep, _tile_plan((16, 32))
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    _assert_embedding_is_a_product(text)
    assert "all-reduce(" in text or "all-reduce-start(" in text


def test_four_chip_fused_step_attention_reads_the_packed_product(
    topo, tpu_branches, mesh4
):
    """The same on the 2x2 data mesh: the packed call goes through
    ``shard_map`` over the batch axis as the three-tensor call does,
    each chip's two images."""
    rep = NamedSharding(mesh4, P())
    state = _abstract_state(_vit_stem_former(), rep)
    step = make_mesh_fused_step(
        state, mesh4, loss_fn=chip_smoke.former_loss
    )
    compiled = _lower_fused_tile(
        step, state, 2, rep, _tile_plan((16, 32))
    ).compile()
    text = _assert_fits_with_kernel(compiled)
    _assert_attention_reads_the_packed_product(text, B // 4)
    assert "all-reduce(" in text or "all-reduce-start(" in text


def test_unsharded_kernel_in_a_partitioned_program_is_refused(
    topo, tpu_branches, mesh4
):
    """No mesh passed means "single-device program": when that is not
    true the lowering refuses — it does not quietly take another path."""
    by_batch = NamedSharding(mesh4, P("data"))
    th, tw = 16, 32
    n = (H // th) * (W // tw)
    fn = jax.jit(lambda r, i, tl: T.decode_tile_delta(r, i, tl, (H, W, C)))
    with pytest.raises(NotImplementedError, match="shard_map"):
        fn.lower(
            _sds((n, th, tw, C), jnp.uint8, NamedSharding(mesh4, P())),
            _sds((B, 160), jnp.int32, by_batch),
            _sds((B, 160, th, tw, C), jnp.uint8, by_batch),
        )


@pytest.mark.slow
def test_echo_fused_step_compiles(topo):
    """Reservoir gather + re-augmentation + update in one program, at
    frame size (no kernel of ours inside; the question is whether the
    chip takes the uint8 ring gather and the augment chain)."""
    from blendjax.data.echo import SampleReservoir, default_echo_augment

    one = SingleDeviceSharding(topo.devices[0])
    res = SampleReservoir(capacity=2 * B, augment=default_echo_augment(), rng=0)
    res.insert({
        "image": np.zeros((B, H, W, C), np.uint8),
        "xy": np.zeros((B, 8, 2), np.float32),
    })
    step = make_echo_fused_step(res.draw)
    buffers = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, one), res.draw_token([0])["_echo_buffers"]
    )
    compiled = step.jits["echo"].lower(
        _abstract_state(CubeRegressor(), one), buffers,
        _sds((B,), jnp.int32, one), _sds((), jnp.uint32, one),
    ).compile()
    _assert_fits_with_kernel(compiled, kernel=False)


@pytest.mark.slow
def test_fused_palette_group_compiles(topo):
    """The full-frame palette codec's fused form (``_pal`` groups):
    palette expansion + K'=8 scanned updates."""
    one = SingleDeviceSharding(topo.devices[0])
    frames = np.random.default_rng(0).integers(
        0, 12, (B, H, W, 1), np.uint8
    ).repeat(C, axis=-1)
    packed, palette, bits = T.palettize_frames(frames)
    buf, spec = T.pack_fields({
        "image" + T.FRAMEPAL_SUFFIXES[bits]: packed,
        "image" + T.PALETTE_SUFFIX: palette,
        "xy": np.zeros((B, 8, 2), np.float32),
    })
    step = make_fused_tile_step()
    compiled = step.jits["pal"].lower(
        _abstract_state(CubeRegressor(), one),
        _sds((8, buf.shape[0]), jnp.uint8, one),
        spec, (("image", (H, W, C, bits)),), (),
    ).compile()
    _assert_fits_with_kernel(compiled, kernel=False)
