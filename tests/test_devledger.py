"""The device ledger (blendjax.obs.devledger): HLO collective parsing,
graceful degradation of the compile-time extraction, the retrace audit,
the driver's cost-model MFU hand-off, the doctor's retrace-storm /
memory-bound arms, and the reporter/flight-bundle surfaces."""

import json
import os
import types

import numpy as np
import pytest

from blendjax.obs import diagnose
from blendjax.obs.devledger import (
    COLLECTIVE_KINDS,
    COLLECTIVE_METRICS,
    HBM_GAUGES,
    LEDGER_GAUGES,
    UNAVAILABLE,
    V5E_PEAK_FLOPS,
    ExecutableLedger,
    RetraceAudit,
    batch_signature,
    chip_peak_flops,
    default_peak_flops,
    ledger as global_ledger,
    measure_model_flops,
    parse_collectives,
)
from blendjax.utils.metrics import Metrics


# -- HLO collective parsing --------------------------------------------------


def test_parse_collectives_iota_groups_and_axis_attribution():
    hlo = (
        "%ar = f32[256]{0} all-reduce(%p0), "
        "replica_groups=[2,4]<=[8], to_apply=%add\n"
    )
    out = parse_collectives(hlo, mesh_axes={"data": 4, "model": 2})
    assert out["ops"] == 1
    assert out["per_kind"]["all-reduce"] == 256 * 4
    assert out["total_bytes"] == 1024
    # iota group size is the SECOND number: [2,4]<=[8] is 2 groups of 4,
    # which matches the size-4 "data" axis
    assert out["per_axis"] == {"data": 1024}


def test_parse_collectives_brace_groups_and_dtype_widths():
    hlo = (
        "%ag = bf16[8,16]{1,0} all-gather(%p0), "
        "replica_groups={{0,1},{2,3}}, dimensions={0}\n"
    )
    out = parse_collectives(hlo, mesh_axes={"x": 2, "y": 8})
    assert out["per_kind"]["all-gather"] == 8 * 16 * 2  # bf16 is 2 bytes
    assert out["per_axis"] == {"x": 256}


def test_parse_collectives_done_counted_once_on_start():
    hlo = (
        "%s = (f32[64]{0}, f32[64]{0}) all-reduce-start(%p1), "
        "replica_groups=[1,8]<=[8]\n"
        "%d = f32[64]{0} all-reduce-done(%s)\n"
    )
    out = parse_collectives(hlo)
    assert out["ops"] == 1  # the -done line adds nothing


def test_parse_collectives_unmatched_group_lands_under_unknown():
    hlo = (
        "%ar = f32[32]{0} all-reduce(%p0), "
        "replica_groups=[2,4]<=[8], to_apply=%add\n"
    )
    out = parse_collectives(hlo, mesh_axes={"data": 3})
    assert out["per_axis"] == {"unknown": 128}


def test_parse_collectives_every_kind_recognized():
    hlo = (
        "%a = f32[8]{0} all-reduce(%p0), replica_groups=[1,2]<=[2]\n"
        "%b = f32[8]{0} all-gather(%p0), replica_groups=[1,2]<=[2]\n"
        "%c = f32[8]{0} reduce-scatter(%p0), replica_groups=[1,2]<=[2]\n"
        "%d = f32[8]{0} collective-permute(%p0), "
        "source_target_pairs={{0,1}}\n"
        "%e = f32[8]{0} all-to-all(%p0), replica_groups=[1,2]<=[2]\n"
    )
    out = parse_collectives(hlo)
    assert out["ops"] == len(COLLECTIVE_KINDS)
    assert all(out["per_kind"][k] == 32 for k in COLLECTIVE_KINDS)
    assert out["total_bytes"] == 32 * 5


def test_parse_collectives_empty_hlo():
    out = parse_collectives("ENTRY %main { %p = f32[4]{0} parameter(0) }")
    assert out == {
        "total_bytes": 0, "ops": 0,
        "per_kind": {k: 0 for k in COLLECTIVE_KINDS}, "per_axis": {},
    }


# -- batch signatures --------------------------------------------------------


def test_batch_signature_sorted_mask_kept_underscores_scalars_dropped():
    arr = types.SimpleNamespace
    batch = {
        "image": arr(shape=(4, 8, 8, 4), dtype="uint8"),
        "_seq": arr(shape=(4,), dtype="int64"),
        "_mask": arr(shape=(4,), dtype="float32"),
        "scalar": arr(shape=(), dtype="float32"),
    }
    assert batch_signature(batch) == (
        ("_mask", (4,), "float32"),
        ("image", (4, 8, 8, 4), "uint8"),
    )


# -- compile-time extraction: good path and graceful degradation -------------


class _MemAnalysis:
    argument_size_in_bytes = 1000
    output_size_in_bytes = 200
    temp_size_in_bytes = 300
    generated_code_size_in_bytes = 50
    alias_size_in_bytes = 100


class _GoodCompiled:
    def cost_analysis(self):
        return [{"flops": 1200.0, "bytes accessed": 3400.0}]

    def memory_analysis(self):
        return _MemAnalysis()

    def as_text(self):
        return (
            "%ar = f32[64]{0} all-reduce(%p0), "
            "replica_groups=[1,4]<=[4], to_apply=%add\n"
        )


class _BrokenCompiled:
    def cost_analysis(self):
        return None

    def memory_analysis(self):
        raise RuntimeError("backend has no memory analysis")

    def as_text(self):
        raise RuntimeError("no HLO text")


def test_register_extracts_and_publishes_gauges():
    reg = Metrics()
    led = ExecutableLedger(registry=reg)
    sig = (("image", (8, 16, 16, 4), "uint8"), ("xy", (8, 8, 2), "float32"))
    entry = led.register("step", _GoodCompiled(), signature=sig,
                         mesh={"data": 4})
    assert entry["flops"] == 1200.0
    assert entry["bytes_accessed"] == 3400.0
    # donated/aliased buffers counted once in the peak
    assert entry["hbm_peak_bytes"] == 1000 + 200 + 300 + 50 - 100
    assert entry["batch_images"] == 8
    assert entry["collectives"]["per_axis"] == {"data": 256}
    g = reg.report()["gauges"]
    assert g["device.flops_per_step"] == 1200.0
    assert g["device.hbm_peak_bytes"] == 1450
    assert g["device.collective_bytes"] == 256
    assert g["device.collective.all_reduce_bytes"] == 256
    assert g["device.collective.all_gather_bytes"] == 0
    assert "device.ledger_failures" not in reg.report()["counters"]


def test_register_degrades_to_unavailable_and_never_raises():
    reg = Metrics()
    led = ExecutableLedger(registry=reg)
    entry = led.register("broken", _BrokenCompiled())
    assert entry["flops"] == UNAVAILABLE
    assert entry["bytes_accessed"] == UNAVAILABLE
    assert entry["hbm_peak_bytes"] == UNAVAILABLE
    assert entry["temp_bytes"] == UNAVAILABLE
    assert entry["collectives"] == UNAVAILABLE
    rep = reg.report()
    assert rep["counters"]["device.ledger_failures"] == 3
    # unavailable fields stay out of the gauges entirely
    assert not any(k.startswith("device.") for k in rep["gauges"])
    # and the structured report still serializes
    json.dumps(led.report())


def test_register_empty_cost_analysis_degrades_only_that_field():
    class _EmptyCost(_GoodCompiled):
        def cost_analysis(self):
            return []

    reg = Metrics()
    led = ExecutableLedger(registry=reg)
    entry = led.register("partial", _EmptyCost())
    assert entry["flops"] == UNAVAILABLE
    assert entry["hbm_peak_bytes"] == 1450  # memory half still lands
    assert reg.report()["counters"]["device.ledger_failures"] == 1
    assert reg.report()["gauges"]["device.hbm_peak_bytes"] == 1450
    assert "device.flops_per_step" not in reg.report()["gauges"]


def test_flops_per_image_prefers_matching_then_largest_lead():
    led = ExecutableLedger(registry=Metrics())

    class _Flops(_GoodCompiled):
        def __init__(self, flops):
            self._f = flops

        def cost_analysis(self):
            return [{"flops": self._f, "bytes accessed": 0.0}]

    led.register("a", _Flops(800.0),
                 signature=(("image", (4, 8, 8, 4), "uint8"),))
    led.register("b", _Flops(1600.0),
                 signature=(("image", (8, 8, 8, 4), "uint8"),))
    assert led.flops_per_image() == 1600.0 / 8
    assert led.flops_per_image(batch_images=4) == 800.0 / 4
    assert led.flops_per_image(batch_images=99) == 1600.0 / 8  # fallback


def test_catalog_tuples_cover_the_documented_family():
    # the BJX123 contract gate enumerates these module-level catalogs;
    # pin their shape so a rename keeps docs and code in one motion
    assert len(COLLECTIVE_METRICS) == len(COLLECTIVE_KINDS)
    assert all(m.startswith("device.collective.") for m in COLLECTIVE_METRICS)
    assert len(LEDGER_GAUGES) == 8 and len(HBM_GAUGES) == 4
    assert all(m.startswith("device.") for m in LEDGER_GAUGES + HBM_GAUGES)


# -- runtime HBM poll --------------------------------------------------------


def test_poll_memory_is_a_graceful_noop_on_cpu():
    reg = Metrics()
    led = ExecutableLedger(registry=reg)
    assert led.poll_memory(reg) is None
    assert not any(k.startswith("device.hbm") for k in reg.report()["gauges"])
    assert led.report()["memory"] in (None, {"supported": False})


def test_default_peak_flops_on_cpu_is_none():
    # tier-1 runs on JAX_PLATFORMS=cpu: no chip, no utilization
    assert default_peak_flops() is None


def test_chip_peaks_are_keyed_by_exact_device_kind():
    """The kind a v5e reports is in the table; anything else is an
    error naming the kind — no substring guess, no silent None."""
    assert chip_peak_flops("TPU v5 lite") == (V5E_PEAK_FLOPS, "TPU v5e")
    for kind in ("TPU v5 lite pod", "tpu v5 lite", "TPU v44", ""):
        with pytest.raises(KeyError, match="no peak FLOP/s on record"):
            chip_peak_flops(kind)


# -- retrace events and the audit --------------------------------------------


class _Flight:
    def __init__(self):
        self.dumps = []

    def dump(self, **kw):
        self.dumps.append(kw)


def test_note_retrace_counts_attributes_and_fires_flight_once():
    reg = Metrics()
    led = ExecutableLedger(registry=reg)
    fl = _Flight()
    led.attach_flight(fl, threshold=2)
    sig = (("image", (6, 8, 8, 4), "uint8"),)
    led.note_retrace(sig)
    assert not fl.dumps
    led.note_retrace(sig)
    assert len(fl.dumps) == 1  # threshold crossed
    led.note_retrace(sig)
    assert len(fl.dumps) == 1  # one-shot
    assert reg.report()["counters"]["device.retraces"] == 3
    rep = led.report()["retraces"]
    assert rep["count"] == 3
    assert "(6, 8, 8, 4)" in rep["events"][0]["signature"]


def test_retrace_audit_counts_unbucketed_shape_exactly_once():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    reg = Metrics()
    led = ExecutableLedger(registry=reg)
    f = jax.jit(lambda x: x + 1)
    audit = RetraceAudit(f, warmup=1, ledger=led)
    assert audit.active
    x4 = jnp.zeros((4,))
    f(x4)
    assert audit.observe({"image": x4}) is False  # warm-up baseline
    f(x4)
    assert audit.observe({"image": x4}) is False  # cache hit
    x6 = jnp.zeros((6,))
    f(x6)
    assert audit.observe({"image": x6}) is True  # unbucketed: counted
    f(x6)
    assert audit.observe({"image": x6}) is False  # now cached: once only
    assert led.retrace_count == 1
    ev = led.report()["retraces"]["events"]
    assert "(6,)" in ev[0]["signature"]
    assert reg.report()["counters"]["device.retraces"] == 1


def test_retrace_audit_inactive_without_a_jit_cache():
    assert RetraceAudit.for_step(lambda x: x) is None


def test_retrace_audit_unwraps_aot_fallback_step():
    jax = pytest.importorskip("jax")

    wrapper = types.SimpleNamespace(_step=jax.jit(lambda x: x))
    assert RetraceAudit.for_step(wrapper) is not None


# -- the doctor's device arms ------------------------------------------------


def _report(spans=None, counters=None, gauges=None):
    return {
        "spans": {
            k: {"count": 10, "total_s": v} for k, v in (spans or {}).items()
        },
        "counters": counters or {},
        "gauges": gauges or {},
        "histograms": {},
    }


def test_doctor_retrace_storm():
    v = diagnose(_report(
        spans={"train.dispatch": 2.0},
        counters={"device.retraces": 3},
    ))
    assert v.kind == "retrace-storm"
    assert "device.retraces=3" in v.reason
    assert "pad" in v.advice or "bucket" in v.advice


def test_doctor_retraces_below_threshold_not_a_storm():
    v = diagnose(_report(
        spans={"train.dispatch": 2.0},
        counters={"device.retraces": 2},
    ))
    assert v.kind != "retrace-storm"


def test_doctor_memory_bound_temp_dominant_names_scratch():
    v = diagnose(_report(
        spans={"train.dispatch": 2.0},
        gauges={"device.hbm_headroom_frac": 0.05,
                "device.temp_bytes": 800.0,
                "device.hbm_peak_bytes": 1000.0},
    ))
    assert v.kind == "memory-bound"
    assert "temporaries" in v.reason


def test_doctor_memory_bound_resident_state_names_fsdp_lever():
    v = diagnose(_report(
        spans={"train.dispatch": 2.0},
        gauges={"device.hbm_headroom_frac": 0.03,
                "device.temp_bytes": 100.0,
                "device.hbm_peak_bytes": 1000.0},
    ))
    assert v.kind == "memory-bound"
    assert "resident state" in v.reason
    assert "fsdp" in v.advice


def test_doctor_healthy_headroom_not_memory_bound():
    v = diagnose(_report(
        spans={"train.dispatch": 2.0},
        gauges={"device.hbm_headroom_frac": 0.5},
    ))
    assert v.kind != "memory-bound"


# -- reporter and flight-bundle surfaces -------------------------------------


def test_reporter_jsonl_carries_device_block(tmp_path):
    from blendjax.obs import StatsReporter
    from blendjax.obs.lineage import FrameLineage

    reg = Metrics()
    reg.gauge("device.flops_per_step", 10.0)
    reg.count("device.retraces", 1)
    path = str(tmp_path / "stats.jsonl")
    rep = StatsReporter(interval_s=3600, registry=reg,
                        lineage=FrameLineage(), jsonl_path=path)
    rep.tick()
    rec = json.loads(open(path).read().strip())
    assert rec["device"]["device.flops_per_step"] == 10.0
    assert rec["device"]["device.retraces"] == 1


def test_flight_bundle_contains_device_ledger(tmp_path):
    from blendjax.obs.watchdog import FlightRecorder

    global_ledger.reset()
    try:
        global_ledger._entries.append({"name": "t", "flops": 1.0})
        global_ledger._retraces.append({
            "signature": "(('image', (6,), 'float32'),)",
            "count": 1, "cache_size": 2,
        })
        rec = FlightRecorder(str(tmp_path))
        bundle = rec.dump(reason="test", registry=Metrics())
        data = json.load(open(os.path.join(bundle, "device_ledger.json")))
        assert data["entries"][0]["name"] == "t"
        assert data["retraces"]["count"] == 1
        assert "(6,)" in data["retraces"]["events"][0]["signature"]
    finally:
        global_ledger.reset()


# -- driver wiring (cost-model MFU hand-off) ---------------------------------


def _small_batch(batch=4):
    return {
        "image": np.zeros((batch, 16, 16, 4), np.uint8),
        "xy": np.zeros((batch, 8, 2), np.float32),
    }


def test_driver_build_adopts_cost_model_flops():
    pytest.importorskip("jax")
    from blendjax.models import CubeRegressor
    from blendjax.train.driver import TrainDriver

    global_ledger.reset()
    try:
        drv = TrainDriver.build(
            CubeRegressor(features=(2,)), _small_batch(), aot=True,
            buckets=(2,), inflight=2, sync_every=0, peak_flops=1e12,
        )
        assert drv.stats["mfu_source"] == "cost-model"
        assert drv.flops_per_image and drv.flops_per_image > 0
        # adoption reads the full-batch (lead 4) entry exactly
        entries = [
            e for e in global_ledger.report()["entries"]
            if e["batch_images"] == 4 and isinstance(e["flops"], float)
        ]
        assert entries
        assert drv.flops_per_image == entries[-1]["flops"] / 4
    finally:
        global_ledger.reset()


def test_driver_hand_fed_flops_override_wins():
    pytest.importorskip("jax")
    from blendjax.models import CubeRegressor
    from blendjax.train.driver import TrainDriver

    global_ledger.reset()
    try:
        drv = TrainDriver.build(
            CubeRegressor(features=(2,)), _small_batch(), aot=True,
            buckets=(2,), inflight=2, sync_every=0,
            flops_per_image=123.0, peak_flops=1e12,
        )
        assert drv.stats["mfu_source"] == "hand-fed"
        assert drv.flops_per_image == 123.0
    finally:
        global_ledger.reset()


def test_measure_model_flops_memo_and_small_geometry():
    pytest.importorskip("jax")
    from blendjax.obs.devledger import _FLOPS_MEMO

    out = measure_model_flops(shape=(16, 16), batch=2)
    assert out["flops_per_image"] > 0
    assert ("CubeRegressor", (16, 16), 2, None) in _FLOPS_MEMO
    assert measure_model_flops(shape=(16, 16), batch=2) == out  # memo hit


def _cnn_forward_flops(h, w):
    """CubeRegressor's matmul FLOPs an image, forward: four stride-2 3x3
    convolutions (32, 64, 128, 256 features) and the dense head."""
    flops, cin = 0, 4
    for f in (32, 64, 128, 256):
        h, w = h // 2, w // 2
        flops += 2 * 9 * cin * f * h * w
        cin = f
    return flops + 2 * 256 * 256 + 2 * 256 * 16


def _former_forward_flops(h, w, patch, dim, depth):
    """StreamFormer's matmul FLOPs an image, forward: the patch
    embedding, then per block qkv, scores, values, projection and the
    4x MLP, then the 16-output head."""
    t = (h // patch) * (w // patch)
    block = 2 * t * dim * 3 * dim + 4 * t * t * dim + 2 * t * dim * dim \
        + 4 * t * dim * 4 * dim
    return 2 * t * patch * patch * 4 * dim + depth * block + 2 * dim * 16


@pytest.mark.parametrize("model", ["CubeRegressor", "StreamFormer"])
def test_measure_model_flops_matches_analytic_count(model):
    """XLA's cost analysis of the unchunked step against a count from
    shapes (forward + twice that backward), per model the probe serves:
    a ``lax.scan`` body counted once, or a model whose shapes drifted,
    moves the MFU denominator without failing anything else."""
    pytest.importorskip("jax")
    if model == "CubeRegressor":
        shape = (480, 640)
        got = measure_model_flops(shape=shape, memo=False)
        want = 3 * _cnn_forward_flops(*shape)
    else:
        import chip_smoke
        from blendjax.models import StreamFormer

        shape, former = (64, 96), dict(patch=16, dim=64, depth=2)
        got = measure_model_flops(
            model=StreamFormer(num_heads=2, num_outputs=16, **former),
            loss_fn=chip_smoke.former_loss, shape=shape, memo=False,
        )
        want = 3 * _former_forward_flops(*shape, **former)
    assert 0.7 * want < got["flops_per_image"] < 1.3 * want, (got, want)
