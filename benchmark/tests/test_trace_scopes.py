"""Device time by part of the step, from the trace's own operation
metadata: the raw walker and the reduction against the trace PR 22
recorded on the v5e (no scope of ours in it yet) and the one PR 24
recorded with the scopes in, and against a trace written by hand whose
times and name stacks are known because they were chosen."""

import gzip
import os
import shutil
import time

import pytest

import cells
import trace_scopes
from xplane_writer import _bytes, _int

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1_000.0


def unpacked(name: str, tmp_path) -> str:
    path = str(tmp_path / name.replace(".gz", ""))
    with gzip.open(os.path.join(HERE, name), "rb") as src, open(
        path, "wb"
    ) as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def v5e(tmp_path_factory):
    return unpacked(
        "v5e_cube_step_x3.xplane.pb.gz", tmp_path_factory.mktemp("v5e")
    )


# -- the walker, against the chip's own file ----------------------------------------


def test_walker_reads_the_metadata_stats_of_the_v5e_trace(v5e):
    (ops,) = trace_scopes.op_metadata(v5e).values()  # the one device plane
    by_lhs = {name.split(" ")[0]: stats for name, stats in ops.items()}
    expand = by_lhs["%fusion.1"]
    assert expand["tf_op"] == "jit(_fused)/vmap(vmap())/gather:"
    assert expand["hlo_category"] == "custom fusion"
    assert expand["bytes_accessed"] == 146800640
    assert expand["flops"] == 0 and expand["program_id"]
    kernel = by_lhs["%_fused.1"]
    assert kernel["tf_op"] == "jit(_fused)/pallas_call:"
    assert kernel["bytes_accessed"] == 0  # a custom call reports none


def test_by_scope_reproduces_the_hand_reading_of_the_v5e_trace(v5e):
    """ISSUE 24's table: share of the step program's self time by the
    head of the name stack, to 0.1 of a percent. The trace holds one
    whole execution of three."""
    got = trace_scopes.by_scope(v5e)
    assert got["executions"] == 1 and got["devices"] == 1
    all_of_it = sum(got["seconds"].values())
    assert all_of_it == pytest.approx(0.12988, rel=1e-3)

    def share(*heads):
        return 100.0 * sum(
            s for tf_op, s in got["seconds"].items()
            if any(tf_op.startswith(h) for h in heads)
        ) / all_of_it

    call = "jit(_fused)/while/body/closed_call/"
    assert share("jit(_fused)/vmap(vmap())/gather:") == pytest.approx(37.7, abs=0.1)
    assert share(call + "transpose(jvp(CubeRegressor))") == pytest.approx(25.3, abs=0.1)
    assert share(call + "jvp(CubeRegressor)") == pytest.approx(21.1, abs=0.1)
    assert share("jit(_fused)/pallas_call:") == pytest.approx(7.7, abs=0.1)
    assert share("jit(_fused)/while/body/dynamic_slice:") == pytest.approx(6.0, abs=0.1)
    assert share("jit(_fused)/reshape;") == pytest.approx(1.5, abs=0.1)
    assert 100.0 * got["seconds"][""] / all_of_it == pytest.approx(0.5, abs=0.1)
    # no scope of ours yet: forward and backward are found, the decode
    # and the optimizer are in the rest
    part = {
        p: 100.0 * trace_scopes.seconds_of(got, [p]) / all_of_it
        for p in trace_scopes.PARTS
    }
    assert part["forward"] == pytest.approx(21.1, abs=0.1)
    assert part["backward"] == pytest.approx(25.3, abs=0.1)
    assert part["decode"] == part["optimizer"] == part["reshard"] == 0.0
    assert sum(part.values()) == pytest.approx(100.0)


def test_the_scoped_v5e_trace_splits_by_name(tmp_path):
    """The same three executions recorded in PR 24 with the scopes in
    (``record_v5e_step.py``; the program is the same one: the scopes
    change metadata, and ``program_id`` did not move). Now the decode
    and the kernel are found by name, and the parts add up to the busy
    time ``reduce_trace`` reads from the same execution."""
    import reduce_trace

    path = unpacked("v5e_cube_step_scoped_x3.xplane.pb.gz", tmp_path)
    got = trace_scopes.by_scope(path)
    assert got["executions"] == 1 and got["devices"] == 1
    ms = {
        p: 1e3 * trace_scopes.seconds_of(got, [p]) for p in trace_scopes.PARTS
    }
    assert ms["decode"] == pytest.approx(61.075, abs=0.01)  # 47.0 %
    assert ms["forward"] == pytest.approx(27.385, abs=0.01)  # 21.1 %
    assert ms["backward"] == pytest.approx(32.885, abs=0.01)  # 25.3 %
    # XLA fuses each parameter's adam update into the fusion that makes
    # its gradient (named after the backward's op): only the rest of the
    # update carries the optimizer's name
    assert ms["optimizer"] == pytest.approx(0.049, abs=0.005)
    assert ms["rest"] == pytest.approx(8.492, abs=0.01)  # the scan's slicing
    assert ms["reshard"] == 0.0
    (step,) = reduce_trace.summarize(
        reduce_trace.read_planes(path)
    )["modules"].values()
    assert sum(ms.values()) == pytest.approx(
        1e3 * step["busy_s_per_execution"], rel=1e-4
    )
    assert 1e3 * trace_scopes.seconds_of(
        got, ["palette_expand"]
    ) == pytest.approx(49.01, abs=0.05)
    (kernel,) = [
        (s, meta) for op, (s, meta) in got["ops"].items()
        if "tpu_custom_call" in op
    ]
    assert got["ops"] and kernel[1]["tf_op"] == (
        "jit(_fused)/decode/tile_decode_spatial/tile_decode_spatial/pallas_call:"
    )
    assert 1e3 * kernel[0] == pytest.approx(9.32, abs=0.05)
    # PR 22's trace of the same program, before the names
    (before,) = trace_scopes.op_metadata(
        unpacked("v5e_cube_step_x3.xplane.pb.gz", tmp_path)
    ).values()
    assert {m["program_id"] for m in before.values() if m["program_id"]} == {
        kernel[1]["program_id"]
    }


# -- name stacks ---------------------------------------------------------------------

CALL = "jit(_fused)/while/body/closed_call/"


@pytest.mark.parametrize(
    "tf_op, part",
    [
        ("jit(_fused)/decode/vmap(vmap(palette_expand))/gather:", "decode"),
        ("jit(_fused)/decode/tile_decode_spatial/tile_decode_spatial/pallas_call:", "decode"),
        ("jit(_fused)/shmap_body/vmap(decode)/scatter:", "decode"),
        # a longer identifier is another name
        ("jit(_fused)/tile_decode_spatial/pallas_call:", "rest"),
        ("jit(_fused)/predecode/gather:", "rest"),
        ("jit(_fused)/reshard/sharding_constraint:", "reshard"),
        (CALL + "jvp(StreamFormer)/block0/MultiHeadAttention_0/attn_core/exp:", "forward"),
        (CALL + "jvp()/div:", "forward"),  # the loss outside the model
        (CALL + "transpose(jvp(StreamFormer))/block0/MultiHeadAttention_0/attn_core/mul:", "backward"),
        (CALL + "transpose(jvp())/div:", "backward"),
        # fused from both passes: the first rule met decides
        (CALL + "jvp(M)/mul:;" + CALL + "transpose(jvp(M))/mul:", "backward"),
        (CALL + "optimizer/sqrt:", "optimizer"),
        (CALL + "add:", "rest"),  # what the optimizer read as before its scope
        ("jit(_fused)/while/body/dynamic_slice:", "rest"),
        ("", "rest"),
        (None, "rest"),
    ],
)
def test_every_operation_lands_in_exactly_one_part(tf_op, part):
    assert trace_scopes.part_of(tf_op) == part
    assert [
        p for p in trace_scopes.PARTS if trace_scopes.matches(tf_op, [p])
    ] == [part]


def test_a_scope_is_matched_across_the_parts():
    fwd = CALL + "jvp(StreamFormer)/block3/MultiHeadAttention_0/attn_core/exp:"
    bwd = CALL + "transpose(jvp(StreamFormer))/block3/MultiHeadAttention_0/attn_core/mul:"
    mlp = CALL + "jvp(StreamFormer)/block3/Dense_0/dot_general:"
    assert trace_scopes.matches(fwd, ["attn_core"])
    assert trace_scopes.matches(bwd, ["attn_core"])
    assert not trace_scopes.matches(mlp, ["attn_core"])
    assert not trace_scopes.matches(bwd, ["attn_core"], exclude=["backward"])
    assert trace_scopes.matches(fwd, ["attn_core"], exclude=["backward"])


# -- a trace written by hand ------------------------------------------------------------

STACKS = {
    "%expand": "jit(_fused)/decode/vmap(vmap(palette_expand))/gather:",
    "%tile_decode_spatial.1": "jit(_fused)/decode/tile_decode_spatial/tile_decode_spatial/pallas_call:",
    "%pin": "jit(_fused)/reshard/sharding_constraint:",
    "%while": None,  # the loop itself carries no stack
    "%slice": "jit(_fused)/while/body/dynamic_slice:",
    "%scores": CALL + "jvp(StreamFormer)/block0/MultiHeadAttention_0/attn_core/exp:",
    "%mlp": CALL + "jvp(StreamFormer)/block0/Dense_0/dot_general:",
    "%dscores": CALL + "transpose(jvp(StreamFormer))/block0/MultiHeadAttention_0/attn_core/mul:",
    "%dmlp": CALL + "transpose(jvp(StreamFormer))/block0/Dense_0/dot_general:",
    "%adam": CALL + "optimizer/sqrt:",
}


def execution(t0: float, scale: float = 1.0) -> list:
    """One execution of 1000 us x ``scale`` starting at ``t0`` us:
    decode 100 + 50, reshard 10, a loop of 800 holding slice 20, forward
    150 + 100, backward 250 + 200, optimizer 60; the loop's own 20 and
    the 40 before and between are nobody's."""
    def ev(name, start, end):
        return (name, (t0 + start * scale) * US, (t0 + end * scale) * US)

    return [
        ev("%expand", 0, 100), ev("%tile_decode_spatial.1", 100, 150),
        ev("%pin", 150, 160), ev("%while", 180, 980), ev("%slice", 180, 200),
        ev("%scores", 200, 350), ev("%mlp", 350, 450),
        ev("%dscores", 450, 700), ev("%dmlp", 700, 900),
        ev("%adam", 900, 960),
    ]


def written(tmp_path, name="hand.xplane.pb") -> str:
    """Window 0..4000 us; four executions, the first and the last cut by
    the trace (and three times as slow, so that counting them would
    show), the two in the middle whole, the second 10 % slower."""
    ops = (
        [(n, s, e) for n, s, e in execution(0, 3.0) if e <= 1000 * US]
        + execution(1000) + execution(2000, 1.1)
        + [(n, s, e) for n, s, e in execution(3100, 3.0) if e <= 4000 * US]
    )
    modules = [
        ("jit__fused(7)", 0.0, 1000 * US), ("jit__fused(7)", 1000 * US, 2000 * US),
        ("jit__fused(7)", 2000 * US, 3085 * US), ("jit__fused(7)", 3100 * US, 4000 * US),
        ("jit_tiny(9)", 3088 * US, 3095 * US),  # another program, whole
    ]
    stat_names = {1: "tf_op", 2: "hlo_category", 3: "bytes_accessed", 4: "stack"}
    ids = {name: i for i, name in enumerate(STACKS, 1)}
    ids.update({"jit__fused(7)": 100, "jit_tiny(9)": 101})

    def line(lid, line_name, events):
        body = _int(1, lid) + _bytes(2, line_name.encode()) + _int(3, 0)
        for op, s, e in events:
            body += _bytes(4, _int(1, ids[op]) + _int(2, int(s * 1000))
                           + _int(3, int((e - s) * 1000)))
        return _bytes(3, body)

    device = _int(1, 1) + _bytes(2, b"/device:TPU:0")
    device += line(1, "XLA Ops", ops) + line(2, "XLA Modules", modules)
    for op, mid in ids.items():
        meta = _int(1, mid) + _bytes(2, op.encode())
        stack = STACKS.get(op)
        if stack is not None:
            # the stack as a ref to a stat_metadata name, as the chip's
            # profiler writes it, a category as a string, bytes as uint64
            stat_names[1000 + mid] = stack
            meta += _bytes(5, _int(1, 1) + _int(7, 1000 + mid))
            meta += _bytes(5, _int(1, 2) + _bytes(5, b"fusion"))
            meta += _bytes(5, _int(1, 3) + _int(3, 4096 * mid))
        device += _bytes(4, _int(1, mid) + _bytes(2, meta))
    for sid, sname in stat_names.items():
        device += _bytes(5, _int(1, sid) + _bytes(
            2, _int(1, sid) + _bytes(2, sname.encode())
        ))
    host = _int(1, 2) + _bytes(2, b"/host:CPU") + _bytes(3, (
        _int(1, 1) + _bytes(2, b"main") + _int(3, 0)
        + _bytes(4, _int(1, 1) + _int(2, 0) + _int(3, int(4000 * US * 1000)))
    )) + _bytes(4, _int(1, 1) + _bytes(
        2, _int(1, 1) + _bytes(2, b"bench.trace_window")
    ))
    path = str(tmp_path / name)
    with open(path, "wb") as f:
        f.write(_bytes(1, device) + _bytes(1, host))
    return path


def test_walker_reads_refs_strings_and_integers(tmp_path):
    (ops,) = trace_scopes.op_metadata(written(tmp_path)).values()
    assert ops["%adam"] == {
        "tf_op": CALL + "optimizer/sqrt:", "hlo_category": "fusion",
        "bytes_accessed": 4096 * 10, "flops": None, "program_id": None,
    }
    assert ops["%while"]["tf_op"] is None
    assert len(ops) == len(STACKS) + 2


def test_only_whole_executions_of_the_step_program_count(tmp_path):
    got = trace_scopes.by_scope(written(tmp_path))
    assert got["executions"] == 2 and got["devices"] == 1
    ms = {
        p: 1e3 * trace_scopes.seconds_of(got, [p]) for p in trace_scopes.PARTS
    }
    mean = (1.0 + 1.1) / 2  # the two whole executions, per execution
    assert ms["decode"] == pytest.approx(0.150 * mean)
    assert ms["reshard"] == pytest.approx(0.010 * mean)
    assert ms["forward"] == pytest.approx(0.250 * mean)
    assert ms["backward"] == pytest.approx(0.450 * mean)
    assert ms["optimizer"] == pytest.approx(0.060 * mean)
    # the loop's own 20 us and the slice; the 40 us nobody ran are not time
    assert ms["rest"] == pytest.approx(0.040 * mean)
    assert 1e3 * trace_scopes.seconds_of(got, ["attn_core"]) == pytest.approx(
        0.400 * mean
    )
    assert 1e3 * trace_scopes.seconds_of(
        got, ["attn_core"], exclude=["backward"]
    ) == pytest.approx(0.150 * mean)
    assert 1e3 * trace_scopes.seconds_of(
        got, ["palette_expand"]
    ) == pytest.approx(0.100 * mean)
    assert 1e3 * trace_scopes.seconds_of(
        got, ["tile_decode_spatial"]
    ) == pytest.approx(0.050 * mean)


def test_a_trace_with_no_whole_execution_gives_nothing(tmp_path):
    import xplane_writer

    path = str(tmp_path / "cut.xplane.pb")
    xplane_writer.write(path, {
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 0.0, 90 * US), ("fusion.1", 100 * US, 200 * US)],
            "XLA Modules": [("jit__fused(1)", 0.0, 90 * US),
                            ("jit__fused(1)", 100 * US, 200 * US)],
        },
    })
    assert trace_scopes.by_scope(path) is None


# -- the readers ---------------------------------------------------------------------

READERS = {
    name: cells.load_module("readers", name)
    for name in ("trace_scope_ms_per_update", "trace_unattributed_share",
                 "spans_share")
}


def obs_of(trace, chunk=2):
    return {
        "trace": trace,
        "window": {"chunk": chunk, "seconds": 10.0,
                   "t0_mono": time.monotonic() - 10.0},
        "spans": {
            "driver.ring_wait": {"count": 7, "total_s": 5.5},
            "driver.loss_sync": {"count": 2, "total_s": 2.5},
            "driver.drain_wait": {"count": 1, "total_s": 1.75},
            "tiles.pack": {"count": 9, "total_s": 0.1},
        },
    }


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(cells, "OUT", str(tmp_path / "out"))
    monkeypatch.setattr(trace_scopes, "_parsed", {})
    where = tmp_path / "out" / "traces" / "some_cell" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    return where


def test_readers_read_the_run_s_own_trace(tmp_path, out_dir):
    written(out_dir, "host.xplane.pb")
    obs = obs_of({"modules": {}})
    read = READERS["trace_scope_ms_per_update"].read
    mean = 1.05
    assert read(obs, include=["decode"]) == pytest.approx(0.150 * mean / 2)
    assert read(obs, include=["attn_core"]) == pytest.approx(0.400 * mean / 2)
    # a name no operation carries is a program from before the scopes
    assert read(obs, include=["no_such_scope"]) is None
    parts = [
        read(obs, include=[p]) for p in trace_scopes.PARTS
    ]
    assert sum(parts) == pytest.approx(0.960 * mean / 2)
    assert READERS["trace_unattributed_share"].read(obs) == pytest.approx(
        100.0 * 40 / 960
    )
    assert len(trace_scopes._parsed) == 1  # parsed once for all of them


def test_an_older_trace_is_not_this_run_s(out_dir):
    path = written(out_dir, "old.xplane.pb")
    an_hour_ago = time.time() - 3600
    os.utime(path, (an_hour_ago, an_hour_ago))
    obs = obs_of({"modules": {}})
    assert READERS["trace_scope_ms_per_update"].read(obs, include=["decode"]) is None
    assert READERS["trace_unattributed_share"].read(obs) is None


def test_readers_return_nothing_without_a_trace(out_dir):
    written(out_dir, "rehearsal.xplane.pb")  # a rehearsal writes one too
    obs = obs_of(None)
    assert READERS["trace_scope_ms_per_update"].read(obs, include=["decode"]) is None
    assert READERS["trace_unattributed_share"].read(obs) is None
    assert trace_scopes._parsed == {}  # and nothing was parsed


def test_spans_share_adds_the_waits_up():
    read = READERS["spans_share"].read
    waits = ["driver.ring_wait", "driver.loss_sync", "driver.drain_wait"]
    assert read(obs_of(None), spans=waits) == pytest.approx(97.5)
    # a span the window never opened adds nothing (the parent commit
    # has no driver.drain_wait)
    obs = obs_of(None)
    del obs["spans"]["driver.drain_wait"]
    assert read(obs, spans=waits) == pytest.approx(80.0)
    obs["spans"] = {}
    assert read(obs, spans=waits) is None


def test_host_spans_are_the_program_s_own(tmp_path):
    import xplane_writer

    path = str(tmp_path / "host.xplane.pb")
    xplane_writer.write(path, {
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 0.0, 90 * US)]},
        "/host:CPU": {
            "main": [
                ("bench.trace_window", 0.0, 100 * US),
                ("bench.submit", 10 * US, 20 * US),
                ("train.dispatch", 11 * US, 19 * US),
                ("driver.ring_wait", 30 * US, 90 * US),
                ("PjitFunction(_fused)", 12 * US, 18 * US),
            ],
            "ingest": [("ingest.recv.shard0", 5 * US, 25 * US)],
        },
    })
    assert trace_scopes.host_spans(path) == [
        ("ingest.recv.shard0", 5 * US, 25 * US),
        ("train.dispatch", 11 * US, 19 * US),
        ("driver.ring_wait", 30 * US, 90 * US),
    ]
