"""A kernel's share of its roofline: the work functions against hand
counts, and the reader on traces whose times are known because they were
chosen (``xplane_writer``)."""

import time

import pytest

import cells
import trace_scopes
import xplane_writer

US = 1e3  # ns
WORK = cells.load_module("flops/kernels", "flash_attention")
READER = cells.load_module("readers", "trace_kernel_roofline_share")
VIT = dict(patch=16, dim=768, depth=12, num_heads=12)
FRAME = (480, 640, 4)
PEAK, HBM = 197e12, 819e9  # peaks.json, TPU v5 lite


def test_vit_b16_against_hand_counts():
    product = 2 * 8 * 12 * 1200 * 1200 * 64  # one T x T x D product, all heads
    assert product == pytest.approx(17.69e9, rel=1e-3)
    tensor = 8 * 1200 * 768 * 2  # q, k, v, o, ... in bf16: 14.7 MB each
    fwd = WORK.required(VIT, FRAME, 8, "bf16", "forward")
    bwd = WORK.required(VIT, FRAME, 8, "bf16", "backward")
    assert fwd == {"flops": 12 * 2 * product, "bytes": 12 * 4 * tensor}
    assert bwd == {"flops": 12 * 4 * product, "bytes": 12 * 8 * tensor}
    assert fwd["bytes"] / 12 == pytest.approx(59e6, rel=1e-2)
    # both compute-bound on the v5e: 0.180 and 0.359 ms a layer
    assert READER.least_seconds(fwd, "TPU v5 lite") / 12 == pytest.approx(
        0.1796e-3, rel=1e-3
    )
    assert READER.least_seconds(bwd, "TPU v5 lite") / 12 == pytest.approx(
        0.3592e-3, rel=1e-3
    )
    assert fwd["bytes"] / HBM < fwd["flops"] / PEAK


def test_the_count_is_of_the_input_s_tokens_and_of_what_a_chip_sees():
    s = WORK.shape(VIT, FRAME, 8)
    assert s["tokens"] == 1200 and s["head_dim"] == 64  # 1,200, never 1,280
    one = WORK.required(VIT, FRAME, 8, "bf16", "forward")
    # the mesh cell: batch 32 over 4 chips is a chip's 8
    assert WORK.required(VIT, FRAME, 32 // 4, "bf16", "forward") == one
    assert WORK.required(VIT, FRAME, 16, "bf16", "forward")["flops"] == 2 * one["flops"]
    # a causal configuration requires the lower triangle only
    causal = WORK.required(dict(VIT, causal=True), FRAME, 8, "bf16", "backward")
    full = WORK.required(VIT, FRAME, 8, "bf16", "backward")
    assert causal["flops"] / full["flops"] == pytest.approx(1201 / 2400)
    assert causal["bytes"] == full["bytes"]
    f32 = WORK.required(VIT, FRAME, 8, "f32", "forward")
    assert f32["bytes"] == 2 * one["bytes"] and f32["flops"] == one["flops"]
    # a memory-bound shape is bounded by its bytes: one token a head
    tiny = WORK.required(dict(VIT, patch=480), (480, 480, 4), 8, "bf16", "forward")
    assert tiny["bytes"] / HBM > tiny["flops"] / PEAK


def kernel_trace(where, name, fwd_us, bwd_us, launches="flash_attention",
                 layers=2, chunk=2):
    """Four executions of 1,000 us (the first and the last are cut by
    the trace's own start and stop and never counted); in each, ``chunk``
    updates x ``layers`` forward launches of ``fwd_us`` and backward ones
    of ``bwd_us``, beside a fusion."""
    ops, modules = [], []
    for x in range(4):
        t0 = 1000.0 * x
        modules.append(("jit__fused(7)", t0 * US, (t0 + 1000) * US))
        ops.append(("%fusion.1 = f32[8]{0} fusion()", t0 * US, (t0 + 100) * US))
        t = t0 + 100
        for u in range(chunk):
            for layer in range(layers):
                for kind, us in (("fwd", fwd_us), ("bwd", bwd_us)):
                    if launches is None:
                        continue
                    ops.append((
                        f"%{launches}_{kind}.{layer + 1} = (bf16[8,1280,768]"
                        "{2,1,0}) custom-call(), custom_call_target="
                        '"tpu_custom_call"', t * US, (t + us) * US,
                    ))
                    t += us
        ops.append(("%flash_attention_fwd_pad.3 = bf16[8,1280,768]{2,1,0} "
                    "fusion()", 900 * US + t0 * US, 950 * US + t0 * US))
    path = where / name
    xplane_writer.write(str(path), {
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
    })
    return path


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(cells, "OUT", str(tmp_path / "out"))
    monkeypatch.setattr(trace_scopes, "_parsed", {})
    where = tmp_path / "out" / "traces" / "cell" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    return where


def obs_of(kwargs, chunk=2, batch=8):
    return {
        "trace": {"modules": {}},
        "window": {"chunk": chunk, "t0_mono": time.monotonic() - 10.0},
        "device": {"kind": "TPU v5 lite"},
        "model": {"kwargs": kwargs, "input_shape": FRAME,
                  "batch_per_chip": batch, "precision": "bf16"},
    }


ARGS = {
    "fwd": dict(kernel="flash_attention_fwd", work="flash_attention", which="forward"),
    "bwd": dict(kernel="flash_attention_bwd", work="flash_attention", which="backward"),
}


def test_a_kernel_of_known_time_and_shapes_reads_its_share(out_dir):
    kernel_trace(out_dir, "known.xplane.pb", fwd_us=40.0, bwd_us=90.0)
    kwargs = dict(VIT, depth=2)
    obs = obs_of(kwargs)
    for which, us in (("fwd", 40.0), ("bwd", 90.0)):
        work = WORK.required(kwargs, FRAME, 8, "bf16", ARGS[which]["which"])
        want = 100.0 * (work["flops"] / PEAK) / (2 * us * 1e-6)
        got = READER.read(obs, **ARGS[which])
        assert got == pytest.approx(want, rel=1e-6)
    # forward: 2 layers x 35.39 GFLOP = 359.3 us at peak over 80 us measured
    # is over 100 %: the reader hides no wrong count behind a min()
    assert READER.read(obs, **ARGS["fwd"]) > 100.0
    # the pad around the kernel carries its name as a prefix, not its name
    scopes = trace_scopes.this_run(obs)
    assert READER.kernel_seconds(scopes, "flash_attention_fwd") == pytest.approx(
        2 * 2 * 40e-6
    )


def test_a_trace_without_the_kernel_reads_nothing(out_dir):
    kernel_trace(out_dir, "xla.xplane.pb", 40.0, 90.0, launches=None)
    obs = obs_of(dict(VIT, depth=2))
    assert trace_scopes.this_run(obs) is not None
    assert READER.read(obs, **ARGS["fwd"]) is None
    assert READER.read(obs, **ARGS["bwd"]) is None
    assert READER.read(dict(obs, trace=None), **ARGS["fwd"]) is None


@pytest.mark.parametrize("padded_to", [1200, 1280])
def test_padded_and_unpadded_launches_require_the_same_work(
    out_dir, padded_to
):
    """The launch's own shape (1,280 rows where the kernel pads) is in
    the operation's name and not in the count: the share of a padded
    launch falls only by the time the padding costs."""
    us = round(40.0 * (padded_to / 1200) ** 2, 2)  # whole picoseconds
    kernel_trace(out_dir, f"t{padded_to}.xplane.pb", fwd_us=us, bwd_us=2 * us)
    kwargs = dict(VIT, depth=2)
    work = WORK.required(kwargs, FRAME, 8, "bf16", "forward")
    got = READER.read(obs_of(kwargs), **ARGS["fwd"])
    assert got == pytest.approx(
        100.0 * (work["flops"] / PEAK) / (2 * us * 1e-6), rel=1e-6
    )
    assert work["flops"] == 2 * 2 * 2 * 8 * 12 * 1200 * 1200 * 64
