"""bjx-lint (blendjax.analysis) tests: one true positive AND one true
negative per rule, inline-suppression and baseline mechanics, CLI exit
codes, and the self-gate (the repo itself stays clean)."""

import json
import os
import subprocess
import sys
import textwrap
import time

from blendjax.analysis import (
    analyze_paths,
    analyze_source,
    load_baseline,
    write_baseline,
)
from blendjax.analysis.core import all_rules, apply_baseline

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def findings(source, relpath="mod.py", select=None):
    return analyze_source(
        textwrap.dedent(source), relpath, select=set(select) if select else None
    )


def rule_ids(source, relpath="mod.py", select=None):
    return [f.rule for f in findings(source, relpath, select)]


# -- BJX101 jit-purity ------------------------------------------------------


def test_bjx101_flags_side_effects_in_jit_decorated_function():
    got = findings(
        """
        import time

        import jax
        import numpy as np

        @jax.jit
        def step(x):
            print("x =", x)
            t = time.time()
            noise = np.random.rand(4)
            return x + noise + t
        """
    )
    assert [f.rule for f in got] == ["BJX101"] * 3
    assert "print()" in got[0].message
    assert "time.time" in got[1].message
    assert "numpy.random" in got[2].message


def test_bjx101_reaches_through_call_graph_and_partial_and_lambda():
    got = findings(
        """
        import functools

        import jax

        def helper(x):
            print(x)
            return x

        @functools.partial(jax.jit, static_argnames=("k",))
        def outer(x, k=1):
            return helper(x) * k

        def wrap(x):
            return jax.jit(lambda y: print(y))(x)
        """
    )
    quals = {f.message.split("'")[1] for f in got}
    assert quals == {"helper", "<lambda>"}


def test_bjx101_negative_host_side_code_and_jax_random():
    assert (
        rule_ids(
            """
            import jax

            def host_loop(batches):
                for b in batches:
                    print("host logging is fine outside jit", b)

            @jax.jit
            def step(x, key):
                noise = jax.random.normal(key, x.shape)
                jax.debug.print("traced-safe {x}", x=x)
                return x + noise
            """
        )
        == []
    )


def test_bjx101_global_mutation_flagged_but_readonly_global_is_not():
    got = findings(
        """
        import jax

        _step_count = 0
        _config = {}

        @jax.jit
        def counted(x):
            global _step_count
            _step_count = _step_count + 1
            return x

        @jax.jit
        def reader(x):
            global _config
            return x * len(_config)
        """
    )
    assert [f.rule for f in got] == ["BJX101"]
    assert "_step_count" in got[0].message


# -- BJX102 host-sync-in-hot-path -------------------------------------------

HOT_SYNC = """
    import jax
    import numpy as np

    def feed(batches):
        for b in batches:
            db = jax.device_put(b)
            db.block_until_ready()
            x = float(np.asarray(db))
            yield x
"""


def test_bjx102_flags_sync_in_hot_module():
    got = findings(HOT_SYNC, relpath="blendjax/data/pipeline.py")
    assert [f.rule for f in got] == ["BJX102"] * 3


def test_bjx102_hot_marker_opts_a_module_in():
    marked = "# bjx: hot-path\n" + textwrap.dedent(HOT_SYNC)
    assert all(
        f.rule == "BJX102" for f in analyze_source(marked, "anywhere.py")
    )
    assert len(analyze_source(marked, "anywhere.py")) == 3


def test_bjx102_marker_in_docstring_does_not_opt_in():
    doc = '"""Module that merely DOCUMENTS the bjx: hot-path marker."""\n'
    assert analyze_source(doc + textwrap.dedent(HOT_SYNC), "anywhere.py") == []


def test_bjx102_negative_outside_hot_path_and_benign_hot_code():
    # same sync code in a non-hot module: silent
    assert rule_ids(HOT_SYNC, relpath="blendjax/train/bench_tool.py") == []
    # hot module doing async placement only: silent
    assert (
        rule_ids(
            """
            import jax

            def feed(batches):
                for b in batches:
                    yield jax.device_put(b)
            """,
            relpath="blendjax/data/pipeline.py",
        )
        == []
    )


# -- BJX106 sync-on-inflight-step -------------------------------------------

DRIVER_SYNC = """
    import jax
    import numpy as np

    def run(step, state, batches):
        for b in batches:
            state, m = step(state, b)
            jax.block_until_ready(m["loss"])
            v = float(np.asarray(m["loss"]))
        return state
"""


def test_bjx106_flags_same_iteration_sync_in_driver_module():
    got = findings(DRIVER_SYNC, relpath="blendjax/train/driver.py")
    assert [f.rule for f in got] == ["BJX106"] * 3
    assert "block_until_ready()" in got[0].message
    assert "'m'" in got[0].message


def test_bjx106_marker_opts_a_module_in():
    marked = "# bjx: driver-hot-path\n" + textwrap.dedent(DRIVER_SYNC)
    got = analyze_source(marked, "anywhere.py")
    assert [f.rule for f in got] == ["BJX106"] * 3


def test_bjx106_negatives_prior_iteration_and_non_driver_modules():
    # the sanctioned driver shapes: syncs on ring-popped values from
    # EARLIER iterations (helper methods, no same-iteration assign)
    clean = """
        import collections

        import jax
        import numpy as np

        def run(step, state, batches, inflight=4):
            pending = collections.deque()
            for b in batches:
                while len(pending) >= inflight:
                    _wait(pending)
                state, m = step(state, b)
                pending.append(m["loss"])
            return state, float(np.asarray(pending.pop()))

        def _wait(pending):
            oldest = pending.popleft()
            jax.block_until_ready(oldest)
    """
    assert rule_ids(clean, relpath="blendjax/train/driver.py") == []
    # identical per-iteration sync outside driver hot paths: silent
    assert rule_ids(DRIVER_SYNC, relpath="blendjax/train/loops.py") == []
    # sync placed BEFORE the dispatch reads the PREVIOUS iteration's
    # value — the sanctioned sync-one-behind shape, not flagged
    one_behind = """
        import numpy as np

        def run(step, state, batches):
            m = None
            for b in batches:
                if m is not None:
                    print(float(np.asarray(m["loss"])))
                state, m = step(state, b)
            return state
    """
    assert rule_ids(one_behind, relpath="blendjax/train/driver.py") == []


def test_bjx106_item_and_attribute_form():
    got = findings(
        """
        def run(step, state, batches):
            for b in batches:
                state, m = step(state, b)
                x = m["loss"].item()
            return state
        """,
        relpath="blendjax/train/driver.py",
    )
    assert [f.rule for f in got] == ["BJX106"]
    assert "item()" in got[0].message


# -- BJX107 metric-name-cardinality -----------------------------------------

METRIC_NAMES = """
    from blendjax.utils.metrics import metrics

    def consume(items):
        for i, item in enumerate(items):
            metrics.count(f"ingest.item{i}")
            key = "ingest." + item["kind"]
            metrics.count(key)
            with metrics.span("ingest.consume.{}".format(item["kind"])):
                pass
"""


def test_bjx107_flags_computed_names_in_hot_module():
    got = findings(METRIC_NAMES, relpath="blendjax/data/pipeline.py")
    assert [f.rule for f in got] == ["BJX107"] * 3
    assert "f-string" in got[0].message
    assert "variable 'key'" in got[1].message
    assert "str.format()" in got[2].message


def test_bjx107_marker_opts_a_module_in():
    marked = "# bjx: hot-path\n" + textwrap.dedent(METRIC_NAMES)
    got = analyze_source(marked, "anywhere.py")
    assert [f.rule for f in got] == ["BJX107"] * 3
    # the identical code outside a hot path is silent (cold-path
    # cardinality is still a smell, but not this rule's gate)
    assert rule_ids(METRIC_NAMES, relpath="blendjax/cold.py") == []


def test_bjx107_negatives_constant_names_aliases_and_non_registry():
    clean = """
        from blendjax.utils.metrics import metrics as reg

        def consume(items, results):
            for item in items:
                reg.count("ingest.items")
                reg.gauge("ingest.queue_depth", len(items))
                reg.observe(name="ingest.bytes", value=item["n"])
                with reg.span("ingest.consume"):
                    pass
                # not a registry: same method names on another object
                results.count(f"whatever.{item}")
    """
    assert rule_ids(clean, relpath="blendjax/data/pipeline.py") == []


def test_bjx107_alias_import_and_duck_typed_registry_are_covered():
    got = findings(
        """
        from blendjax.utils.metrics import metrics as reg

        class Ingest:
            def __init__(self, metrics):
                self.metrics = metrics

            def consume(self, key):
                reg.count(f"a.{key}")
                self.metrics.count("b." + key)
        """,
        relpath="blendjax/data/batcher.py",
    )
    assert [f.rule for f in got] == ["BJX107"] * 2


def test_bjx107_inline_suppression():
    src = """
        from blendjax.utils.metrics import metrics

        def per_shard(idx):
            name = f"ingest.recv.shard{idx}"
            with metrics.span(name):  # bjx: ignore[BJX107]
                pass
    """
    assert rule_ids(src, relpath="blendjax/data/pipeline.py") == []


# -- BJX108 reservoir-host-materialization -----------------------------------

RESERVOIR_FETCH = """
    # bjx: driver-hot-path
    import numpy as np

    def draw(reservoir, idx):
        batch = reservoir.sample(idx)
        imgs = np.asarray(batch["image"])
        loss = float(batch["xy"])
        return imgs, loss
"""


def test_bjx108_flags_host_fetch_of_sample_result():
    got = findings(RESERVOIR_FETCH, select=["BJX108"])
    assert [f.rule for f in got] == ["BJX108"] * 2
    assert "numpy.asarray()" in got[0].message
    assert "'batch'" in got[0].message


def test_bjx108_flags_direct_nesting_and_constructed_locals():
    src = """
        # bjx: driver-hot-path
        import numpy as np
        from blendjax.data.echo import SampleReservoir

        def insert_and_peek(batches, idx):
            res = SampleReservoir(64)
            for b in batches:
                res.insert(b)
            return np.asarray(res.sample(idx))

        def peek_item(self, idx):
            return self.reservoir.gather(idx)["image"].item()
    """
    got = findings(src, select=["BJX108"])
    assert [f.rule for f in got] == ["BJX108"] * 2
    assert {"insert_and_peek", "peek_item"} == {
        f.message.split("'")[1] for f in got
    }


def test_bjx108_negatives_host_indices_and_unmarked_modules():
    # the sanctioned shape: accounting on the HOST-chosen index vector,
    # device batch never materialized
    clean = """
        # bjx: driver-hot-path
        import numpy as np

        def draw(reservoir, use, rng, b):
            idx = rng.choice(np.flatnonzero(use < 8), size=b)
            batch = reservoir.sample(idx)
            fresh = int((use[idx] == 0).sum())
            np.add.at(use, idx, 1)
            return batch, fresh
    """
    assert rule_ids(clean, select=["BJX108"]) == []
    # a fetch BEFORE the sample assignment reads an unrelated value
    one_behind = """
        # bjx: driver-hot-path
        import numpy as np

        def draw(reservoir, idx, batch):
            host = np.asarray(batch)
            batch = reservoir.sample(idx)
            return host, batch
    """
    assert rule_ids(one_behind, select=["BJX108"]) == []
    # same fetch outside driver hot paths: silent (eval/test code may
    # materialize freely)
    assert rule_ids(
        RESERVOIR_FETCH.replace("# bjx: driver-hot-path", ""),
        select=["BJX108"],
    ) == []


def test_bjx108_inline_suppression():
    src = """
        # bjx: driver-hot-path
        import numpy as np

        def debug_draw(reservoir, idx):
            batch = reservoir.sample(idx)
            return np.asarray(batch["image"])  # bjx: ignore[BJX108]
    """
    assert rule_ids(src, select=["BJX108"]) == []


# -- BJX103 unsafe-deserialization ------------------------------------------


def test_bjx103_flags_ungated_pickle():
    got = findings(
        """
        import pickle

        def load(blob):
            return pickle.loads(blob)
        """
    )
    assert [f.rule for f in got] == ["BJX103"]


def test_bjx103_negatives_gated_and_trusted_and_dumps():
    assert (
        rule_ids(
            """
            import pickle

            def load(blob, allow_pickle=False):
                if not allow_pickle:
                    raise ValueError("untrusted")
                return pickle.loads(blob)

            class Reader:
                def __init__(self, path, allow_pickle=False):
                    self.allow_pickle = allow_pickle

                def _open(self, f):
                    return pickle.Unpickler(f)

            def save(obj):
                return pickle.dumps(obj)

            def load_cache(blob):
                # bjx: trusted-source (bytes we wrote ourselves above)
                return pickle.loads(blob)
            """
        )
        == []
    )


# -- BJX104 zmq-thread-affinity ---------------------------------------------


def test_bjx104_flags_socket_crossing_thread_boundary():
    got = findings(
        """
        import threading

        import zmq

        class Pump:
            def __init__(self, ctx):
                self.sock = ctx.socket(zmq.PULL)
                self._thread = threading.Thread(target=self._run)

            def _run(self):
                while True:
                    self._drain()

            def _drain(self):
                self.sock.recv()
        """
    )
    assert [f.rule for f in got] == ["BJX104"]
    assert "self.sock" in got[0].message and "_run" in got[0].message


def test_bjx104_flags_positional_thread_target():
    got = findings(
        """
        import threading

        import zmq

        class Pump:
            def __init__(self, ctx):
                self.sock = ctx.socket(zmq.PULL)
                self._thread = threading.Thread(None, self._run)

            def _run(self):
                self.sock.recv()
        """
    )
    assert [f.rule for f in got] == ["BJX104"]


def test_bjx104_negatives_same_thread_and_annotated():
    # socket created inside the thread target itself: correct affinity
    assert (
        rule_ids(
            """
            import threading

            import zmq

            class Pump:
                def __init__(self, ctx):
                    self.ctx = ctx
                    self._thread = threading.Thread(target=self._run)

                def _run(self):
                    self.sock = self.ctx.socket(zmq.PULL)
                    self.sock.recv()
            """
        )
        == []
    )
    # explicit ownership-transfer annotation
    assert (
        rule_ids(
            """
            import threading

            import zmq

            class Pump:
                def __init__(self, ctx):
                    self.sock = ctx.socket(zmq.PULL)  # bjx: thread-owner
                    self._thread = threading.Thread(target=self._run)

                def _run(self):
                    self.sock.recv()
            """
        )
        == []
    )


# -- BJX105 socket-leak -----------------------------------------------------


def test_bjx105_flags_leak_and_partial_close():
    got = findings(
        """
        import zmq

        def leaky(ctx):
            sock = ctx.socket(zmq.PUSH)
            sock.send(b"x")

        def conditional(ctx, flag):
            sock = ctx.socket(zmq.PULL)
            if flag:
                sock.close()
        """
    )
    assert [f.rule for f in got] == ["BJX105"] * 2
    assert "never closed" in got[0].message
    assert "some paths" in got[1].message


def test_bjx105_using_the_socket_is_not_an_ownership_transfer():
    got = findings(
        """
        import zmq

        def recv_leak(ctx):
            sock = ctx.socket(zmq.PULL)
            msg = sock.recv()
            return msg

        def print_leak(ctx):
            sock = ctx.socket(zmq.PULL)
            print(sock.recv())
        """
    )
    assert [f.rule for f in got] == ["BJX105"] * 2


def test_bjx105_container_store_is_a_transfer():
    assert (
        rule_ids(
            """
            import zmq

            def pooled(ctx, pool):
                sock = ctx.socket(zmq.PUSH)
                pool.append(sock)

            def listed(ctx):
                socks = [ctx.socket(zmq.PUSH) for _ in range(2)]
                extra = ctx.socket(zmq.PULL)
                bundle = (extra, socks)
                return bundle
            """
        )
        == []
    )


def test_bjx105_negatives_finally_with_transfer():
    assert (
        rule_ids(
            """
            import zmq

            def closed(ctx):
                sock = ctx.socket(zmq.PULL)
                try:
                    sock.recv()
                finally:
                    sock.close()

            def managed(ctx):
                with ctx.socket(zmq.PUB) as sock:
                    sock.send(b"x")

            def handed_off(ctx):
                sock = ctx.socket(zmq.PUSH)
                return sock

            class Holder:
                def __init__(self, ctx):
                    self.sock = ctx.socket(zmq.PAIR)
            """
        )
        == []
    )


def test_bjx105_negative_create_and_close_inside_branch_or_loop():
    assert (
        rule_ids(
            """
            import zmq

            def branch(ctx, flag):
                if flag:
                    sock = ctx.socket(zmq.PULL)
                    sock.recv()
                    sock.close()

            def loop(ctx, addrs):
                for a in addrs:
                    sock = ctx.socket(zmq.PUSH)
                    try:
                        sock.connect(a)
                    finally:
                        sock.close()
            """
        )
        == []
    )


def test_bjx102_lambda_body_is_scanned_in_hot_module():
    got = findings(
        """
        def make_waiter():
            return lambda arr: arr.block_until_ready()
        """,
        relpath="blendjax/data/pipeline.py",
    )
    assert [f.rule for f in got] == ["BJX102"]


# -- suppression / baseline / CLI -------------------------------------------

LEAKY = """
    import zmq

    def leaky(ctx):
        sock = ctx.socket(zmq.PUSH)
        sock.send(b"x")
"""


def test_inline_ignore_suppresses_by_rule_and_bare():
    src = """
        import zmq

        def leaky(ctx):
            sock = ctx.socket(zmq.PUSH)  # bjx: ignore[BJX105]
            sock.send(b"x")

        def leaky2(ctx):
            # bjx: ignore
            sock = ctx.socket(zmq.PUSH)
            sock.send(b"x")
    """
    assert rule_ids(src) == []
    # wrong rule id in the marker does NOT suppress
    assert (
        rule_ids(
            """
            import zmq

            def leaky(ctx):
                sock = ctx.socket(zmq.PUSH)  # bjx: ignore[BJX101]
                sock.send(b"x")
            """
        )
        == ["BJX105"]
    )


def test_baseline_roundtrip_suppresses_and_survives_line_shifts(tmp_path):
    mod = tmp_path / "leak.py"
    mod.write_text(textwrap.dedent(LEAKY))
    base = str(tmp_path / "baseline.json")
    got = analyze_paths([str(mod)], root=str(tmp_path))
    assert [f.rule for f in got] == ["BJX105"]
    assert write_baseline(base, got, str(tmp_path)) == 1
    # baselined: nothing reported
    assert apply_baseline(got, load_baseline(base), str(tmp_path)) == []
    # unrelated lines added above: fingerprint (line-content keyed) holds
    mod.write_text("# a new header comment\nX = 1\n" + textwrap.dedent(LEAKY))
    shifted = analyze_paths([str(mod)], root=str(tmp_path))
    assert [f.rule for f in shifted] == ["BJX105"]
    assert apply_baseline(shifted, load_baseline(base), str(tmp_path)) == []
    # a NEW finding is still reported alongside the baselined one
    mod.write_text(
        textwrap.dedent(LEAKY)
        + textwrap.dedent(
            """
            def leaky_b(ctx):
                s2 = ctx.socket(zmq.PULL)
                s2.recv()
            """
        )
    )
    both = analyze_paths([str(mod)], root=str(tmp_path))
    left = apply_baseline(both, load_baseline(base), str(tmp_path))
    assert len(both) == 2 and len(left) == 1
    assert "s2" in left[0].message


def test_baseline_does_not_alias_identical_line_in_new_function(tmp_path):
    """A brand-new violation textually identical to a grandfathered one
    (same source line, earlier in the file, different function) must NOT
    inherit the baselined fingerprint."""
    mod = tmp_path / "leak.py"
    mod.write_text(textwrap.dedent(LEAKY))
    base = str(tmp_path / "baseline.json")
    write_baseline(
        base, analyze_paths([str(mod)], root=str(tmp_path)), str(tmp_path)
    )
    mod.write_text(
        textwrap.dedent(
            """
            import zmq

            def newer(ctx):
                sock = ctx.socket(zmq.PUSH)
                sock.send(b"y")
            """
        )
        + textwrap.dedent(LEAKY)
    )
    left = apply_baseline(
        analyze_paths([str(mod)], root=str(tmp_path)),
        load_baseline(base),
        str(tmp_path),
    )
    assert [f.rule for f in left] == ["BJX105"]
    assert "'newer'" in left[0].message


def test_cli_exit_codes_and_json(tmp_path):
    mod = tmp_path / "fixture.py"
    mod.write_text(textwrap.dedent(LEAKY))
    env = {**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"}

    def run(*args):
        return subprocess.run(
            [sys.executable, "-m", "blendjax.analysis", *args],
            capture_output=True, text=True, cwd=str(tmp_path), env=env,
        )

    bad = run(str(mod), "--format", "json")
    assert bad.returncode == 1
    data = json.loads(bad.stdout)
    assert data[0]["rule"] == "BJX105"

    wrote = run(str(mod), "--write-baseline")
    assert wrote.returncode == 0
    clean = run(str(mod))
    assert clean.returncode == 0, clean.stdout + clean.stderr

    ok = run("--list-rules")
    assert ok.returncode == 0
    for rule_id in (
        "BJX101", "BJX102", "BJX103", "BJX104", "BJX105", "BJX106",
        "BJX107", "BJX108",
    ):
        assert rule_id in ok.stdout


def test_select_restricts_rules():
    src = """
        import pickle
        import zmq

        def both(ctx, blob):
            sock = ctx.socket(zmq.PUSH)
            return pickle.loads(blob)
    """
    assert sorted(rule_ids(src)) == ["BJX103", "BJX105"]
    assert rule_ids(src, select=["BJX103"]) == ["BJX103"]


def test_syntax_error_reports_bjx000():
    got = analyze_source("def broken(:\n", "bad.py")
    assert [f.rule for f in got] == ["BJX000"]


# -- BJX109 wall-clock-duration ----------------------------------------------


def test_bjx109_flags_wall_clock_duration_in_hot_path():
    src = """
        # bjx: hot-path
        import time

        def recv_loop(work):
            t0 = time.time()
            work()
            return time.time() - t0
    """
    got = findings(src, select=["BJX109"])
    assert [f.rule for f in got] == ["BJX109"]
    assert "time.monotonic" in got[0].message


def test_bjx109_checks_driver_modules_by_basename_and_marker():
    src = """
        import time

        def ring_wait():
            start = time.time()
            return time.time() - start
    """
    assert rule_ids(src, relpath="driver.py", select=["BJX109"]) == [
        "BJX109"
    ]
    marked = "# bjx: driver-hot-path\n" + textwrap.dedent(src)
    got = analyze_source(marked, "echo.py", select={"BJX109"})
    assert [f.rule for f in got] == ["BJX109"]


def test_bjx109_negatives_wire_stamps_mixed_clocks_and_unmarked():
    # cross-process staleness math: one side comes off the message,
    # not a local wall-clock read — the sanctioned pattern
    wire = """
        # bjx: hot-path
        import time

        def ingest(msg):
            now = time.time()
            return now - float(msg["_pub_wall"])
    """
    assert rule_ids(wire, select=["BJX109"]) == []
    # mixed clocks (the chrome-trace timebase offset) are not a
    # wall-wall duration
    mixed = """
        # bjx: hot-path
        import time

        def offset():
            return time.perf_counter() - time.time()
    """
    assert rule_ids(mixed, select=["BJX109"]) == []
    # unmarked modules are out of scope (eval/bench code times with
    # wall clocks freely)
    unmarked = """
        import time

        def f():
            t0 = time.time()
            return time.time() - t0
    """
    assert rule_ids(unmarked, select=["BJX109"]) == []


def test_bjx109_monotonic_durations_stay_clean():
    src = """
        # bjx: hot-path
        import time

        def recv_loop(work):
            t0 = time.monotonic()
            work()
            return time.monotonic() - t0
    """
    assert rule_ids(src, select=["BJX109"]) == []


def test_bjx109_inline_suppression():
    src = """
        # bjx: hot-path
        import time

        def f(work):
            t0 = time.time()
            work()
            return time.time() - t0  # bjx: ignore[BJX109]
    """
    assert rule_ids(src, select=["BJX109"]) == []


# -- BJX110 fleet-thread-affinity ---------------------------------------------


def test_bjx110_flags_launcher_lifecycle_in_hot_path():
    src = """
        # bjx: hot-path

        def on_timeout(self):
            self.launcher.assert_alive()
            return True

        def rebalance(launcher, n):
            launcher.scale_to(n)

        def drain(blender_launcher):
            blender_launcher.retire_instance(0, drain=True)
            blender_launcher.wait()
    """
    got = findings(src, select=["BJX110"])
    assert [f.rule for f in got] == ["BJX110"] * 4
    assert "assert_alive" in got[0].message
    assert "control thread" in got[0].message


def test_bjx110_negatives_non_launcher_receivers_and_unmarked():
    # generic wait()s — trackers, events, subprocesses — are out of
    # scope: the receiver gate requires a launcher-like name
    src = """
        # bjx: hot-path

        def publish(tracker, proc, event):
            tracker.wait()
            event.wait(1.0)
            proc.wait(timeout=5)
    """
    assert rule_ids(src, select=["BJX110"]) == []
    # unmarked modules may drive the launcher freely (the controller
    # module itself, bench code, tests)
    unmarked = """
        def control_tick(launcher):
            launcher.scale_to(3)
            launcher.wait()
    """
    assert rule_ids(unmarked, select=["BJX110"]) == []
    # non-lifecycle launcher calls stay clean
    reads = """
        # bjx: hot-path

        def fleet_size(launcher):
            return launcher.active_count()
    """
    assert rule_ids(reads, select=["BJX110"]) == []


def test_bjx110_hot_by_basename_and_inline_suppression():
    src = """
        def iterate(self):
            self.launcher.poll_processes()
    """
    assert rule_ids(src, relpath="pipeline.py", select=["BJX110"]) == [
        "BJX110"
    ]
    suppressed = """
        def iterate(self):
            self.launcher.poll_processes()  # bjx: ignore[BJX110]
    """
    assert rule_ids(
        suppressed, relpath="pipeline.py", select=["BJX110"]
    ) == []


# -- BJX111 mesh-placement ----------------------------------------------------


def test_bjx111_flags_per_device_device_put_loops():
    src = """
        # bjx: mesh-hot-path
        import jax

        def place_loop(mesh, batch):
            out = []
            for d in mesh.devices:
                out.append(jax.device_put(batch, d))
            return out

        def place_comp(batch):
            return [jax.device_put(batch, d) for d in jax.devices()]

        def place_local(batch):
            for d in jax.local_devices():
                jax.device_put(batch, d)
    """
    got = findings(src, select=["BJX111"])
    assert [f.rule for f in got] == ["BJX111"] * 3
    assert "per-device" in got[0].message
    assert "NamedSharding" in got[0].message


def test_bjx111_flags_global_array_host_materialization():
    src = """
        # bjx: mesh-hot-path
        import jax
        import numpy as np

        def assemble(s, v):
            g = jax.make_array_from_process_local_data(s, v)
            host = np.asarray(g)
            return host

        def direct(s, v):
            return np.asarray(
                jax.make_array_from_process_local_data(s, v)
            )

        def shard_walk(g):
            return [s.data for s in g.addressable_shards]
    """
    got = findings(src, select=["BJX111"])
    assert [f.rule for f in got] == ["BJX111"] * 3
    assert "'g'" in got[0].message
    assert "addressable_shards" in got[2].message


def test_bjx111_negatives_single_placement_and_unmarked():
    # the sanctioned pattern: one grouped placement, no device loop
    src = """
        # bjx: mesh-hot-path
        import jax

        def place(batch, sharding):
            return jax.device_put(batch, sharding)

        def over_fields(batch, sharding):
            # loops over FIELDS are fine; the loop var is not a device
            return {k: jax.device_put(v, sharding)
                    for k, v in batch.items()}
    """
    assert rule_ids(src, select=["BJX111"]) == []
    # a fetch of something never bound from a global assembly is fine
    host = """
        # bjx: mesh-hot-path
        import numpy as np

        def pack(rows):
            return np.asarray(rows)
    """
    assert rule_ids(host, select=["BJX111"]) == []
    # unmarked modules (tests, debug tooling) iterate shards freely
    unmarked = """
        def inspect(g):
            return [s.data for s in g.addressable_shards]
    """
    assert rule_ids(unmarked, select=["BJX111"]) == []


def test_bjx111_hot_by_basename_and_inline_suppression():
    src = """
        def inspect(g):
            for s in g.addressable_shards:
                print(s)
    """
    assert rule_ids(src, relpath="mesh_driver.py", select=["BJX111"]) == [
        "BJX111"
    ]
    suppressed = """
        def inspect(g):
            for s in g.addressable_shards:  # bjx: ignore[BJX111]
                print(s)
    """
    assert rule_ids(
        suppressed, relpath="mesh_driver.py", select=["BJX111"]
    ) == []


# -- BJX112 non-donated-train-jit --------------------------------------------


def test_bjx112_flags_undonated_step_jit_in_hot_module():
    src = """
        # bjx: driver-hot-path
        import jax

        def make_step():
            def step(state, batch):
                return state, {}
            return jax.jit(step)
    """
    assert rule_ids(src, select=["BJX112"]) == ["BJX112"]
    # state-named first param triggers even without a step-ish name
    src2 = """
        # bjx: driver-hot-path
        import jax

        def build():
            def evaluate(state, batch):
                return state.params
            return jax.jit(evaluate)
    """
    assert rule_ids(src2, select=["BJX112"]) == ["BJX112"]


def test_bjx112_donation_keyword_presence_satisfies():
    src = """
        # bjx: driver-hot-path
        import jax

        def make_step(donate=True):
            def step(state, batch):
                return state, {}
            return jax.jit(step, donate_argnums=(0,) if donate else ())
    """
    assert rule_ids(src, select=["BJX112"]) == []


def test_bjx112_decorator_form_and_step_module_scope():
    src = """
        import jax

        @jax.jit
        def train_step(state, batch):
            return state
    """
    # steps.py is in scope without a marker (the builders live there)
    assert rule_ids(src, relpath="steps.py", select=["BJX112"]) == [
        "BJX112"
    ]
    # ... an unmarked ordinary module is not
    assert rule_ids(src, relpath="mod.py", select=["BJX112"]) == []


def test_bjx112_non_step_jits_and_suppressions_pass():
    src = """
        # bjx: driver-hot-path
        import jax

        def build():
            draw = jax.jit(lambda bufs, i: bufs[i])
            gather = jax.jit(_gather)
            # segment-anchored name match: 'constrain' must not read
            # as train
            pin = jax.jit(apply_constraint)
            return draw, gather, pin

        def apply_constraint(sb):
            return sb
    """
    assert rule_ids(src, select=["BJX112"]) == []
    suppressed = """
        # bjx: driver-hot-path
        import jax

        def make_eval():
            def eval_step(state, batch):
                return state.params
            # bjx: ignore[BJX112]
            return jax.jit(eval_step)
    """
    assert rule_ids(suppressed, select=["BJX112"]) == []


# -- BJX113 scenario-id-cardinality ------------------------------------------


def test_bjx113_flags_scenario_id_fstring_anywhere():
    # NOT a hot-path module: BJX107 stays silent, BJX113 fires — the
    # scenario-id rule covers every module.
    src = """
        from blendjax.utils.metrics import metrics

        def account(sid, loss):
            metrics.count(f"scenario.{sid}.rows")
            metrics.observe("loss_" + sid, loss)
    """
    assert rule_ids(src, select=["BJX113"]) == ["BJX113", "BJX113"]
    assert rule_ids(src, select=["BJX107"]) == []


def test_bjx113_flags_format_and_bare_variable_forms():
    src = """
        from blendjax.utils.metrics import metrics

        def account(scenario_id, batch):
            metrics.gauge("scenario.{}.fill".format(scenario_id), 1)
            metrics.count(scenario_id)
    """
    assert rule_ids(src, select=["BJX113"]) == ["BJX113", "BJX113"]


def test_bjx113_ignores_constant_and_non_scenario_dynamic_names():
    src = """
        from blendjax.utils.metrics import metrics

        def account(shard, sids):
            metrics.count("scenario.rows", len(sids))
            metrics.gauge("scenario.space_version", 3)
            # dynamic but not scenario identity: BJX107's (hot-path)
            # business, not BJX113's
            metrics.count(f"ingest.shard{shard}.items")
    """
    assert rule_ids(src, select=["BJX113"]) == []


def test_bjx113_non_registry_receivers_untouched():
    src = """
        def f(ledger, sid):
            ledger.count(f"scenario.{sid}")
    """
    assert rule_ids(src, select=["BJX113"]) == []


def test_bjx113_suppressible_inline():
    src = """
        from blendjax.utils.metrics import metrics

        def account(sid):
            # bounded: test fixture with exactly two ids
            # bjx: ignore[BJX113]
            metrics.count(f"scenario.{sid}.rows")
    """
    assert rule_ids(src, select=["BJX113"]) == []


def test_every_rule_registered():
    assert set(all_rules()) == {
        "BJX101", "BJX102", "BJX103", "BJX104", "BJX105", "BJX106",
        "BJX107", "BJX108", "BJX109", "BJX110", "BJX111", "BJX112",
        "BJX113", "BJX114", "BJX115", "BJX116", "BJX117", "BJX118",
        "BJX119", "BJX120", "BJX121", "BJX122", "BJX125", "BJX126",
    }


def test_project_rules_marked_and_skipped_by_per_file_pass():
    rules = all_rules()
    project_ids = {
        "BJX117", "BJX118", "BJX119", "BJX120", "BJX121", "BJX122",
    }
    assert all(rules[r].project for r in project_ids)
    assert all(not rules[r].project for r in set(rules) - project_ids)
    # per-file analysis never runs a project rule (check() is a no-op)
    assert rules["BJX117"].check(None) == ()


# -- BJX114 checkpoint-in-hot-path -------------------------------------------


def test_bjx114_flags_sync_checkpoint_calls_in_driver_hot_path():
    src = """
        # bjx: driver-hot-path
        def loop(self, batches):
            for b in batches:
                self.state, m = self.step(self.state, b)
                self.checkpoint.save(self.steps, self.state)
                self.checkpoint.wait()
    """
    assert rule_ids(src, select=["BJX114"]) == ["BJX114", "BJX114"]


def test_bjx114_flags_dataflow_from_manager_construction():
    src = """
        # bjx: driver-hot-path
        from blendjax.checkpoint import SnapshotManager

        def run(step, state, batches):
            mgr = SnapshotManager("ckpt/")
            for b in batches:
                state, m = step(state, b)
                mgr.save(1, state)
            mgr.restore(state)
    """
    assert rule_ids(src, select=["BJX114"]) == ["BJX114", "BJX114"]


def test_bjx114_driver_basename_always_checked():
    src = """
        def drain_and_save(self):
            self.ckpt_manager.wait_until_finished()
    """
    assert rule_ids(src, relpath="driver.py", select=["BJX114"]) == [
        "BJX114"
    ]


def test_bjx114_async_and_non_checkpoint_receivers_untouched():
    src = """
        # bjx: driver-hot-path
        def loop(self, batches):
            for b in batches:
                self.state, m = self.step(self.state, b)
                self.checkpoint.save_async(self.steps, self.state)
                self.checkpoint.latest_step(wait=False)
                self.driver.request_checkpoint()
                self.queue.wait()       # not a checkpoint receiver
                self.recorder.save(b)   # not a checkpoint receiver
    """
    assert rule_ids(src, select=["BJX114"]) == []


def test_bjx114_silent_outside_hot_path_and_suppressible():
    src = """
        def teardown(self):
            self.checkpoint.save(self.steps, self.state)
    """
    assert rule_ids(src, select=["BJX114"]) == []
    suppressed = """
        # bjx: driver-hot-path
        def teardown(self):
            # the process is exiting: sanctioned sync flush
            # bjx: ignore[BJX114]
            self.checkpoint.wait()
    """
    assert rule_ids(suppressed, select=["BJX114"]) == []


# -- BJX115 host-materialization-in-actor-loop -------------------------------


def test_bjx115_flags_policy_and_reservoir_fetches_in_actor_module():
    src = """
        # bjx: actor-hot-path
        import numpy as np

        def loop(self, obs):
            while True:
                actions = self.policy(self._snapshot, obs)
                a = np.asarray(actions)
                drawn = self.reservoir.sample(idx)
                v = float(drawn)
    """
    assert rule_ids(src, select=["BJX115"]) == ["BJX115", "BJX115"]


def test_bjx115_flags_item_and_block_until_ready_anywhere_in_actor():
    src = """
        # bjx: actor-hot-path
        import jax

        def loop(self, q):
            x = q.item()
            jax.block_until_ready(q)
    """
    assert rule_ids(src, select=["BJX115"]) == ["BJX115", "BJX115"]


def test_bjx115_actor_basename_always_checked_and_nesting_flagged():
    src = """
        import numpy as np

        def loop(self, idx):
            a = np.asarray(self.policy(snap, obs))
    """
    assert rule_ids(src, "rl/actor.py", select=["BJX115"]) == ["BJX115"]


def test_bjx115_env_outputs_and_host_math_stay_clean():
    """Env step results and plain host accounting never lived on a
    device — the rule must not flag the sanctioned actor shape."""
    src = """
        # bjx: actor-hot-path
        import numpy as np

        def loop(self):
            while True:
                obs, reward, done, infos = self.env.step(a)
                o = np.asarray(obs)
                r = float(reward[0])
                ret = float(self._ep_ret[0])
    """
    assert rule_ids(src, select=["BJX115"]) == []


def test_bjx115_silent_outside_actor_modules_and_suppressible():
    src = """
        import numpy as np

        def learner_sync(self):
            snap = np.asarray(self.policy(s, o))
    """
    assert rule_ids(src, select=["BJX115"]) == []
    suppressed = """
        # bjx: actor-hot-path
        import numpy as np

        def probe(self):
            # one-off debugging probe, not the loop
            # bjx: ignore[BJX115]
            a = np.asarray(self.policy(s, o))
    """
    assert rule_ids(suppressed, select=["BJX115"]) == []


# -- self-gate ---------------------------------------------------------------


def test_repo_is_clean_under_baseline():
    """The CI contract: ``python -m blendjax.analysis blendjax/`` exits 0
    — per-file rules AND the whole-program pass."""
    baseline = load_baseline(os.path.join(REPO_ROOT, ".bjx-baseline.json"))
    got = analyze_paths(
        [os.path.join(REPO_ROOT, "blendjax")], root=REPO_ROOT, project=True
    )
    left = apply_baseline(got, baseline, REPO_ROOT)
    assert left == [], "\n".join(f.render() for f in left)


# -- BJX116 host-inflate-in-hot-path -----------------------------------------


def test_bjx116_flags_zlib_inflate_in_hot_path_module():
    src = """
        # bjx: hot-path
        import zlib

        def consume(self, frames):
            for buf in frames:
                data = zlib.decompress(buf)
                dec = zlib.decompressobj()
    """
    assert rule_ids(src, select=["BJX116"]) == ["BJX116", "BJX116"]


def test_bjx116_flags_aliased_import_and_driver_hot_path():
    src = """
        # bjx: driver-hot-path
        from zlib import decompress

        def submit(self, batch):
            raw = decompress(batch["z"])
    """
    assert rule_ids(src, select=["BJX116"]) == ["BJX116"]


def test_bjx116_streaming_basenames_always_checked():
    src = """
        import zlib

        def pump(self):
            return zlib.decompress(self._buf)
    """
    assert rule_ids(
        src, "blendjax/data/pipeline.py", select=["BJX116"]
    ) == ["BJX116"]


def test_bjx116_silent_outside_hot_modules_and_for_compress():
    """The codec implementation (wire.py, unmarked) and compress-side
    calls stay clean — only hot-path inflate is the hazard."""
    src = """
        import zlib

        def decode(buf):
            return zlib.decompress(buf)
    """
    assert rule_ids(src, select=["BJX116"]) == []
    hot_compress = """
        # bjx: hot-path
        import zlib

        def encode(self, raw):
            return zlib.compress(raw, 6)
    """
    assert rule_ids(hot_compress, select=["BJX116"]) == []


def test_bjx116_suppressible_inline():
    src = """
        # bjx: hot-path
        import zlib

        def consume(self, buf):
            # bjx: ignore[BJX116]
            return zlib.decompress(buf)
    """
    assert rule_ids(src, select=["BJX116"]) == []


# -- whole-program pass (ProjectContext + BJX117/118/119) ---------------------

from blendjax.analysis.core import (  # noqa: E402
    ModuleContext,
    analyze_project_modules,
    parse_paths,
)


def project_findings(*sources, select=None):
    """Project-pass findings over one or more dedented module sources
    (named ``pkg/m0.py``, ``pkg/m1.py``, ...)."""
    modules = [
        ModuleContext(textwrap.dedent(src), f"pkg/m{i}.py")
        for i, src in enumerate(sources)
    ]
    return analyze_project_modules(
        modules, select=set(select) if select else None
    )


RACY_WORKER = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            while True:
                self.count += 1

        def snapshot(self):
            return self.count
"""


def test_bjx117_flags_unlocked_write_across_thread_contexts():
    got = project_findings(RACY_WORKER, select=["BJX117"])
    assert [f.rule for f in got] == ["BJX117"]
    assert got[0].identity == "pkg.m0.Worker.count"
    assert "self.count" in got[0].message
    assert "Worker._run" in got[0].message  # the spawned context is named


def test_bjx117_negative_common_lock_over_all_accesses():
    src = """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while True:
                    with self._lock:
                        self.count += 1

            def snapshot(self):
                with self._lock:
                    return self.count
    """
    assert project_findings(src, select=["BJX117"]) == []


def test_bjx117_negative_init_only_config_and_safe_types():
    src = """
        import queue
        import threading

        class Worker:
            def __init__(self):
                self.size = 4            # config: written only here
                self._q = queue.Queue()  # thread-safe value type
                self._stop = threading.Event()

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while not self._stop.is_set():
                    self._q.put(self.size)

            def snapshot(self):
                return self._q.qsize() + self.size
    """
    assert project_findings(src, select=["BJX117"]) == []


def test_bjx117_entry_lockset_covers_locked_helpers():
    """A private helper called ONLY under the lock inherits it (the
    ``tick`` -> ``_tick_locked`` shape): no finding."""
    src = """
        import threading

        class Controller:
            def __init__(self):
                self._lock = threading.Lock()
                self.streak = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while True:
                    self.tick()

            def tick(self):
                with self._lock:
                    self._tick_locked()

            def _tick_locked(self):
                self.streak += 1

            def state(self):
                with self._lock:
                    return self.streak
    """
    assert project_findings(src, select=["BJX117"]) == []


def test_bjx117_thread_shared_marker_demands_locks_without_spawns():
    marked = """
        import threading

        # bjx: thread-shared
        class Reservoir:
            def __init__(self):
                self.lock = threading.RLock()
                self.draws = 0

            def draw(self):
                with self.lock:
                    self.draws += 1

            def stats(self):
                return self.draws
    """
    got = project_findings(marked, select=["BJX117"])
    assert [f.rule for f in got] == ["BJX117"]
    assert got[0].identity == "pkg.m0.Reservoir.draws"
    # same class, no marker: no spawns anywhere -> single context, clean
    unmarked = marked.replace("# bjx: thread-shared", "# (unmarked)")
    assert project_findings(unmarked, select=["BJX117"]) == []


def test_bjx117_cross_module_spawn_graph():
    """A thread spawned in module 0 reaches a class in module 1 through
    a resolvable constructor attribute — the whole-program part."""
    spawner = """
        import threading

        from pkg.m1 import Sink

        class Pump:
            def __init__(self):
                self.sink = Sink()

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while True:
                    self.sink.push(1)
    """
    sink = """
        class Sink:
            def __init__(self):
                self.total = 0

            def push(self, n):
                self.total += n

            def read(self):
                return self.total
    """
    got = project_findings(spawner, sink, select=["BJX117"])
    assert [f.identity for f in got] == ["pkg.m1.Sink.total"]
    assert "Pump._run" in got[0].message


def test_bjx117_suppressible_inline():
    src = RACY_WORKER.replace(
        "                self.count += 1",
        "                # bjx: ignore[BJX117]\n"
        "                self.count += 1",
    )
    assert project_findings(src, select=["BJX117"]) == []


def test_bjx117_executor_submit_is_a_spawn_site():
    src = """
        from concurrent.futures import ThreadPoolExecutor

        class Pool:
            def __init__(self):
                self.done = 0
                self._pool = ThreadPoolExecutor(2)

            def kick(self):
                self._pool.submit(self._work)

            def _work(self):
                self.done += 1

            def read(self):
                return self.done
    """
    got = project_findings(src, select=["BJX117"])
    assert [f.identity for f in got] == ["pkg.m0.Pool.done"]


LOCK_ORDER = """
    import threading

    class Orders:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def one(self):
            with self.a:
                with self.b:
                    pass

        def two(self):
            with self.b:
                with self.a:
                    pass
"""


def test_bjx118_flags_inconsistent_nesting_once_per_pair():
    got = project_findings(LOCK_ORDER, select=["BJX118"])
    assert [f.rule for f in got] == ["BJX118"]
    assert got[0].identity == "pkg.m0.Orders.a<>pkg.m0.Orders.b"
    assert "Orders.two" in got[0].message or "Orders.one" in got[0].message


def test_bjx118_negative_consistent_order_and_same_lock():
    src = """
        import threading

        class Orders:
            def __init__(self):
                self.a = threading.RLock()
                self.b = threading.Lock()

            def one(self):
                with self.a:
                    with self.b:
                        pass

            def two(self):
                with self.a:
                    with self.a:  # reentrant re-acquire, not a pair
                        with self.b:
                            pass
    """
    assert project_findings(src, select=["BJX118"]) == []


def test_bjx118_transitive_through_the_call_graph():
    src = """
        import threading

        class Orders:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()

            def outer_ab(self):
                with self.a:
                    self._take_b()

            def _take_b(self):
                with self.b:
                    pass

            def outer_ba(self):
                with self.b:
                    with self.a:
                        pass
    """
    got = project_findings(src, select=["BJX118"])
    assert [f.identity for f in got] == ["pkg.m0.Orders.a<>pkg.m0.Orders.b"]


BLOCKED = """
    import queue
    import threading

    class Service:
        def __init__(self):
            self._lock = threading.Lock()
            self._cmds = queue.Queue()

        def start(self):
            threading.Thread(target=self._serve, daemon=True).start()

        def _serve(self):
            while True:
                pass

        def wedge(self):
            with self._lock:
                return self._cmds.get()
"""


def test_bjx119_flags_untimed_queue_get_under_contended_lock():
    got = project_findings(BLOCKED, select=["BJX119"])
    assert [f.rule for f in got] == ["BJX119"]
    assert "queue get()" in got[0].message
    assert "Service.wedge" in got[0].message


def test_bjx119_negative_timeouts_nowait_and_unthreaded_classes():
    timed = BLOCKED.replace(
        "self._cmds.get()", "self._cmds.get(timeout=0.25)"
    )
    assert project_findings(timed, select=["BJX119"]) == []
    nonblock = BLOCKED.replace(
        "self._cmds.get()", "self._cmds.get(block=False)"
    )
    assert project_findings(nonblock, select=["BJX119"]) == []
    # positional timeout slot (the documented Queue.get signature)
    positional = BLOCKED.replace(
        "self._cmds.get()", "self._cmds.get(True, 0.25)"
    )
    assert project_findings(positional, select=["BJX119"]) == []
    # no thread ever contends the lock: the same shape is not flagged
    unthreaded = BLOCKED.replace(
        "            threading.Thread(target=self._serve, daemon=True).start()",
        "            pass",
    )
    assert project_findings(unthreaded, select=["BJX119"]) == []


def test_bjx119_flags_socket_send_join_and_wait_under_lock():
    src = """
        import threading

        class Service:
            def __init__(self):
                self._lock = threading.Lock()

            def start(self):
                threading.Thread(target=self._serve, daemon=True).start()

            def _serve(self):
                pass

            def publish(self, chan, t, ev):
                with self._lock:
                    chan.send(b"x")
                    t.join()
                    ev.wait()
    """
    got = project_findings(src, select=["BJX119"])
    assert sorted(f.message.split(" in ")[0] for f in got) == [
        "blocking join()",
        "blocking socket send()",
        "blocking wait()",
    ]


def test_bjx119_condition_wait_and_bounded_calls_are_sanctioned():
    src = """
        import threading

        class Service:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)

            def start(self):
                threading.Thread(target=self._serve, daemon=True).start()

            def _serve(self):
                pass

            def waiter(self, chan, t):
                with self._lock:
                    self._cv.wait()          # releases the lock by design
                    t.join(timeout=2.0)
                    chan.recv(timeoutms=0)
    """
    assert project_findings(src, select=["BJX119"]) == []


def test_bjx119_suppressible_inline():
    src = BLOCKED.replace(
        "                return self._cmds.get()",
        "                # bjx: ignore[BJX119]\n"
        "                return self._cmds.get()",
    )
    assert project_findings(src, select=["BJX119"]) == []


# -- project fingerprints + baseline migration --------------------------------


def test_project_fingerprints_survive_line_shifts_and_rewording(tmp_path):
    root = tmp_path
    mod = tmp_path / "pkg"
    mod.mkdir()
    path = mod / "w.py"
    path.write_text(textwrap.dedent(RACY_WORKER))
    got = analyze_paths([str(mod)], root=str(root), project=True)
    assert [f.rule for f in got] == ["BJX117"]
    baseline = tmp_path / "bl.json"
    write_baseline(str(baseline), got, str(root))
    data = json.load(open(baseline))
    assert data["version"] == 2
    assert data["entries"][0]["identity"] == "pkg.w.Worker.count"
    # shift every line AND change the anchor line's text: the identity
    # fingerprint still matches, so the finding stays grandfathered
    shifted = "# a new leading comment\nX = 1\n" + textwrap.dedent(
        RACY_WORKER
    ).replace("self.count += 1", "self.count = self.count + 2")
    path.write_text(shifted)
    again = analyze_paths([str(mod)], root=str(root), project=True)
    left = apply_baseline(again, load_baseline(str(baseline)), str(root))
    assert left == []


def test_baseline_version_1_files_stay_valid(tmp_path):
    bl = tmp_path / "old.json"
    bl.write_text(json.dumps({
        "version": 1,
        "entries": [{"fingerprint": "cafe", "rule": "BJX102",
                     "path": "x.py", "line": 1, "message": "m"}],
    }))
    assert load_baseline(str(bl)) == {"cafe"}


# -- shared AST cache ----------------------------------------------------------


def test_parse_paths_shares_one_module_context_per_file(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("import threading\n\n\ndef f():\n    return 1\n")
    modules, errors = parse_paths([str(p)], root=str(tmp_path))
    assert errors == [] and len(modules) == 1
    m = modules[0]
    # the by-type index serves repeated queries without re-walking
    import ast as _ast

    assert m.nodes(_ast.Import) and m.nodes(_ast.FunctionDef)
    # the function table is computed once and cached
    assert list(m.iter_functions()) == list(m.iter_functions())
    assert m.modname == "m"


def test_parse_paths_reports_syntax_errors_as_findings(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text("def broken(:\n")
    modules, errors = parse_paths([str(p)], root=str(tmp_path))
    assert modules == []
    assert [f.rule for f in errors] == ["BJX000"]


# -- the racy fixture, end to end ---------------------------------------------


def test_project_pass_flags_the_racy_fixture():
    fixture = os.path.join(REPO_ROOT, "tests", "fixtures", "racy_threads.py")
    got = analyze_paths([fixture], root=REPO_ROOT, project=True)
    rules = sorted({f.rule for f in got})
    assert rules == ["BJX117", "BJX118", "BJX119"], [
        f.render() for f in got
    ]
    by_rule = {f.rule: f for f in got}
    assert by_rule["BJX117"].identity.endswith("Racy.counter")
    assert "<>" in by_rule["BJX118"].identity
    assert "queue get()" in by_rule["BJX119"].message


# -- CLI: --project / --no-project / exit codes --------------------------------


def run_cli(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "blendjax.analysis", *args],
        capture_output=True, text=True, cwd=cwd,
        env={**os.environ, "PYTHONPATH": REPO_ROOT},
    )


def test_cli_project_mode_default_on_and_opt_out(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "w.py").write_text(textwrap.dedent(RACY_WORKER))
    on = run_cli(["pkg"], cwd=str(tmp_path))
    assert on.returncode == 1 and "BJX117" in on.stdout
    off = run_cli(["pkg", "--no-project"], cwd=str(tmp_path))
    assert off.returncode == 0, off.stdout + off.stderr


def test_cli_project_mode_parse_failure_exits_3_with_hint(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "ok.py").write_text("x = 1\n")
    (pkg / "bad.py").write_text("def broken(:\n")
    r = run_cli(["pkg"], cwd=str(tmp_path))
    assert r.returncode == 3
    assert "--no-project" in r.stderr and "BJX000" in r.stderr
    # the quick path still reports the syntax error as a finding
    r2 = run_cli(["pkg", "--no-project"], cwd=str(tmp_path))
    assert r2.returncode == 1 and "BJX000" in r2.stdout


def test_cli_max_seconds_budget(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "ok.py").write_text("x = 1\n")
    ok = run_cli(["pkg", "--max-seconds", "120"], cwd=str(tmp_path))
    assert ok.returncode == 0
    over = run_cli(["pkg", "--max-seconds", "0"], cwd=str(tmp_path))
    assert over.returncode == 4
    assert "budget" in over.stderr


def test_full_repo_lint_fits_the_ci_wall_time_budget():
    """The CI lint job runs with --max-seconds 60; keep generous local
    headroom so slow CI runners still clear it (the shared-AST-cache
    pass runs the full repo in ~2 s on a dev box)."""
    t0 = time.perf_counter()
    analyze_paths(
        [os.path.join(REPO_ROOT, "blendjax")], root=REPO_ROOT, project=True
    )
    assert time.perf_counter() - t0 < 30.0


def test_list_rules_marks_scope():
    r = run_cli(["--list-rules"], cwd=REPO_ROOT)
    assert r.returncode == 0
    assert "BJX117 unlocked-shared-mutation [project]" in r.stdout
    assert "BJX101 jit-purity [file]" in r.stdout


def test_bjx117_lock_name_matching_is_word_boundary():
    """'host_blocks' is a counter, not a lock: a substring match
    silently dropped it from the race analysis (review finding)."""
    src = """
        import threading

        class Worker:
            def __init__(self):
                self.host_blocks = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while True:
                    self.host_blocks += 1

            def snapshot(self):
                return self.host_blocks
    """
    got = project_findings(src, select=["BJX117"])
    assert [f.identity for f in got] == ["pkg.m0.Worker.host_blocks"]
    # real lock spellings still recognized as locks (exempt + with-able)
    lockish = """
        import threading

        class Worker:
            def __init__(self):
                self.lock_a = threading.Lock()
                self.state = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self.lock_a:
                    self.state += 1

            def snapshot(self):
                with self.lock_a:
                    return self.state
    """
    assert project_findings(lockish, select=["BJX117"]) == []


def test_bjx117_nested_public_named_closures_stay_thread_confined():
    """A closure with a public-looking name inside a spawn target runs
    only in its parent's context — it must not be seeded as a 'main'
    entry point (review finding: spurious second context)."""
    src = """
        import threading

        class Confined:
            def __init__(self):
                self.n = 0

            def start(self):
                threading.Thread(target=self._drain, daemon=True).start()

            def _drain(self):
                def flush():
                    self.n += 1
                while True:
                    flush()
    """
    assert project_findings(src, select=["BJX117"]) == []


# -- jit-boundary dataflow rules (BJX120/121/122) ------------------------------


STEP_AND_FEED = """
    import functools

    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, batch):
        return state

    def feed(state, batch):
        batch["_trace"] = {"t0": 0.0}
        return step(state, batch)
"""


def test_bjx120_flags_direct_stamp_into_jit():
    got = project_findings(STEP_AND_FEED, select=["BJX120"])
    assert [f.rule for f in got] == ["BJX120"]
    assert "'_trace'" in got[0].message and "feed" in got[0].message
    assert got[0].identity == "pkg.m0.feed:_trace->jax.jit(step)"


def test_bjx120_pop_and_filtered_rebuild_are_strips():
    clean = """
        import jax

        step = jax.jit(lambda b: b)

        def feed_pop(batch):
            batch["_trace"] = {}
            batch.pop("_trace", None)
            return step(batch)

        def feed_filter(batch):
            batch["_scenario"] = {}
            clean = {k: v for k, v in batch.items() if not k.startswith("_")}
            return step(clean)
    """
    assert project_findings(clean, select=["BJX120"]) == []


def test_bjx120_provenance_through_rebinding_and_dict_copies():
    """Re-binding aliases share taint (in-place pop strips every alias);
    dict(**batch) / dict(batch) / .copy() copies carry the keys."""
    src = """
        import jax

        step = jax.jit(lambda b: b)

        def leak_copy(batch):
            batch["_scenario_rows"] = [1]
            b2 = batch
            b3 = dict(**b2)
            return step(b3)

        def clean_alias_pop(batch):
            batch["_scenario_rows"] = [1]
            b2 = batch
            b2.pop("_scenario_rows", None)
            return step(batch)
    """
    got = project_findings(src, select=["BJX120"])
    assert [f.rule for f in got] == ["BJX120"]
    assert "leak_copy" in got[0].message


def test_bjx120_strip_via_helper_one_call_hop():
    """A helper whose summary strips the sidecars launders the dict —
    including across modules."""
    helper = """
        _STAMPS = ("_trace", "_scenario_rows")

        def scrub(msg):
            for k in _STAMPS:
                msg.pop(k, None)
            return msg
    """
    feeder = """
        import jax

        from pkg.m0 import scrub

        step = jax.jit(lambda b: b)

        def feed(batch):
            batch["_trace"] = {}
            return step(scrub(batch))
    """
    assert project_findings(helper, feeder, select=["BJX120"]) == []


def test_bjx120_leak_through_forwarding_helper_anchors_in_origin():
    """A helper that forwards its argument into a jit makes the CALLER
    the finding site (that is where the fix goes)."""
    src = """
        import jax

        step = jax.jit(lambda b: b)

        def collate(batch):
            return step(batch)

        def feed(batch):
            batch["_trace"] = {}
            return collate(batch)
    """
    got = project_findings(src, select=["BJX120"])
    assert [f.rule for f in got] == ["BJX120"]
    assert "feed" in got[0].message and "'collate'" in got[0].message


def test_bjx120_wrapped_callee_summaries_are_stable():
    """functools.wraps-decorated callees keep their dataflow summaries:
    a decorated scrubber still strips, a decorated stamper still
    taints."""
    src = """
        import functools

        import jax

        def audited(fn):
            @functools.wraps(fn)
            def inner(*a, **k):
                return fn(*a, **k)
            return inner

        step = jax.jit(lambda b: b)

        @audited
        def scrub(batch):
            batch.pop("_trace", None)
            return batch

        @audited
        def mark(batch):
            batch["_trace"] = {}
            return batch

        def clean(batch):
            batch["_trace"] = {}
            return step(scrub(batch))

        def leaky(batch):
            return step(mark(batch))
    """
    got = project_findings(src, select=["BJX120"])
    assert [f.rule for f in got] == ["BJX120"]
    assert "leaky" in got[0].message


def test_bjx120_wire_decode_is_a_taint_source():
    src = """
        import jax

        from blendjax.transport.wire import decode_message

        step = jax.jit(lambda b: b)

        def replay(frames):
            msg = decode_message(frames)
            return step(msg)
    """
    got = project_findings(src, select=["BJX120"])
    assert [f.rule for f in got] == ["BJX120"]
    assert "_seq" in got[0].message


def test_bjx120_inline_suppression():
    src = STEP_AND_FEED.replace(
        "return step(state, batch)",
        "return step(state, batch)  # sanctioned  # bjx: ignore[BJX120]",
    )
    assert project_findings(src, select=["BJX120"]) == []


def test_bjx121_loop_donation_without_rebind():
    src = """
        import jax

        def _step(state, batch):
            return state

        step = jax.jit(_step, donate_argnums=(0,))

        def run(state, batches):
            for b in batches:
                out = step(state, b)
            return out

        def run_clean(state, batches):
            for b in batches:
                state = step(state, b)
            return state
    """
    got = project_findings(src, select=["BJX121"])
    assert [f.rule for f in got] == ["BJX121"]
    assert "inside a loop" in got[0].message and "'run'" in got[0].message


def test_bjx121_tuple_rebind_and_if_merge_are_clean():
    src = """
        import jax

        def _step(state, prio, batch):
            return state, prio

        step = jax.jit(_step, donate_argnums=(0, 1))

        def update(state, prio, batch):
            state, prio = step(state, prio, batch)
            return state, prio

        def branched(state, prio, batch, flag):
            if flag:
                state, prio = step(state, prio, batch)
            else:
                state = state
            return state, prio
    """
    assert project_findings(src, select=["BJX121"]) == []


def test_bjx122_dynamic_keyset_and_bucket_launder():
    src = """
        import jax

        step = jax.jit(lambda b: b)

        def feed(batch, msg):
            batch[msg["name"]] = msg["value"]
            return step(batch)

        def feed_bucketed(batch, msg):
            n = pad_to_bucket(msg["count"])
            cfg = {}
            cfg[n] = 1
            return step(batch)
    """
    got = project_findings(src, select=["BJX122"])
    assert [f.rule for f in got] == ["BJX122"]
    assert "key set" in got[0].message or "gained a key" in got[0].message
    assert "feed" in got[0].message


def test_jit_boundary_fixtures_flag_end_to_end():
    """The acceptance gate: both historical stamp-leak regressions, the
    PR-12 policy-sync shape, and the unbounded-static-arg shape all
    flag through analyze_paths(project=True) — one finding each, with
    the sanctioned twins in the same files staying quiet."""
    expect = {
        "stamp_leak_trace.py": ("BJX120", "feed:_trace->jax.jit(train_step)"),
        "stamp_leak_scenario.py": (
            "BJX120", "EchoSampler.draw:_scenario_rows->"
        ),
        "use_after_donate_sync.py": ("BJX121", "Learner.update:state"),
        "retrace_unbounded.py": ("BJX122", "feed:jax.jit(_decode):n="),
    }
    for name, (rule, ident) in expect.items():
        fixture = os.path.join(REPO_ROOT, "tests", "fixtures", name)
        got = analyze_paths([fixture], root=REPO_ROOT, project=True)
        assert [f.rule for f in got] == [rule], (name, [
            f.render() for f in got
        ])
        assert ident in got[0].identity, (name, got[0].identity)


def test_cli_flags_jit_boundary_fixtures():
    """Same gate through the CLI (exit code 1 + rule id in the text
    output), as the issue's acceptance criterion demands."""
    for name, rule in (
        ("stamp_leak_trace.py", "BJX120"),
        ("stamp_leak_scenario.py", "BJX120"),
        ("use_after_donate_sync.py", "BJX121"),
        ("retrace_unbounded.py", "BJX122"),
    ):
        r = run_cli(
            [os.path.join("tests", "fixtures", name), "--no-baseline"],
            cwd=REPO_ROOT,
        )
        assert r.returncode == 1, (name, r.stdout, r.stderr)
        assert rule in r.stdout, (name, r.stdout)


def test_jit_boundary_fingerprints_survive_line_shifts(tmp_path):
    """Baseline-v2 identities for BJX120/121/122 are line-independent:
    grandfathered findings stay suppressed after the file shifts."""
    mod = tmp_path / "pkg"
    mod.mkdir()
    path = mod / "w.py"
    src = textwrap.dedent(STEP_AND_FEED)
    path.write_text(src)
    got = analyze_paths([str(mod)], root=str(tmp_path), project=True)
    got = [f for f in got if f.rule == "BJX120"]
    assert len(got) == 1
    baseline = tmp_path / "bl.json"
    write_baseline(str(baseline), got, str(tmp_path))
    data = json.load(open(baseline))
    assert data["version"] == 2
    assert data["entries"][0]["identity"] == "pkg.w.feed:_trace->jax.jit(step)"
    path.write_text("# leading comment\nX = 1\n\n" + src)
    again = analyze_paths([str(mod)], root=str(tmp_path), project=True)
    again = [f for f in again if f.rule == "BJX120"]
    left = apply_baseline(again, load_baseline(str(baseline)), str(tmp_path))
    assert left == []

# -- contract-drift gate (BJX123) --------------------------------------------


def _mods(*sources):
    from blendjax.analysis.core import ModuleContext

    return [
        ModuleContext(textwrap.dedent(src), rel)
        for rel, src in sources
    ]


def test_contracts_metric_extraction_variants():
    """Every emission idiom lands in the catalog: direct literal,
    local name-bind, f-string family prefix, ``self.registry``
    receiver, and the ALL-CAPS spec-table loop."""
    from blendjax.analysis.contracts import extract_metrics

    cat = extract_metrics(_mods(("pkg/m.py", """
        TRANSITIONS = ("trace.wire_ms", "trace.step_ms")

        def emit(metrics, idx):
            metrics.count("wire.frames")
            span_name = f"ingest.recv.shard{idx}"
            with metrics.span(span_name):
                pass
            metrics.observe(f"echo.lag{idx}", 1.0)

        class C:
            def tick(self, n):
                self.registry.gauge_max("train.inflight_hwm", n)
                for name in TRANSITIONS:
                    self.registry.observe(name, 0.0)
    """)))
    assert "wire.frames" in cat.names
    assert "train.inflight_hwm" in cat.names
    assert "trace.wire_ms" in cat.names and "trace.step_ms" in cat.names
    assert "ingest.recv.shard" in cat.prefixes
    assert "echo.lag" in cat.prefixes
    # helper calls on non-registry receivers are not metric emissions
    assert not any(n.startswith("self.") for n in cat.names)


def test_contracts_stamp_and_knob_extraction():
    from blendjax.analysis.contracts import (
        extract_env_knobs,
        extract_stamp_keys,
    )

    mods = _mods(("pkg/wire.py", """
        import os

        SEQ_KEY = "_seq"
        NOT_A_KEY = "plain"

        def read():
            os.environ.get("BLENDJAX_MY_KNOB", "0")
            return {"_batched": True}
    """))
    stamps = extract_stamp_keys(mods)
    assert "_seq" in stamps.names
    assert "_batched" in stamps.names  # wire-control literal
    assert "plain" not in stamps.names
    # the analysis layer's sidecar universe is part of the contract
    assert "_trace" in stamps.names and "_mask" in stamps.names
    knobs = extract_env_knobs(mods)
    assert set(knobs.names) == {"BLENDJAX_MY_KNOB"}


def test_contracts_doc_matching_grammar():
    """Doc-side parsing: wildcard families, trailing-N families,
    artifact filenames excluded, and a ``BLENDJAX_MY_*`` family
    reference not read as a knob named with a trailing underscore."""
    from blendjax.analysis.contracts import (
        _doc_metric_live,
        _metric_documented,
        documented_knobs,
        documented_metrics,
        extract_metrics,
    )

    lines = [
        "Counters: `wire.frames`, the `echo.*` family, and per-shard",
        "`ingest.recv.shardN` spans; traces export to `trace.json`.",
        "Every switch is a `BLENDJAX_MY_*` variable —",
        "`BLENDJAX_MY_KNOB` (default 16).",
    ]
    docs = documented_metrics(lines)
    assert "wire.frames" in docs and "echo.*" in docs
    assert "ingest.recv.shardN" in docs
    assert "trace.json" not in docs  # artifact filename, not a metric
    assert _metric_documented("echo.fresh", docs)
    assert not _metric_documented("rl.fresh", docs)
    knobs = documented_knobs(lines)
    assert knobs == {"BLENDJAX_MY_KNOB": 4}
    cat = extract_metrics(_mods(("pkg/m.py", """
        def f(metrics, i):
            metrics.span(f"ingest.recv.shard{i}")
    """)))
    assert _doc_metric_live("ingest.recv.shardN", cat)
    assert not _doc_metric_live("ingest.recv.extra", cat)


def test_contracts_end_to_end_drift_both_ways(tmp_path):
    """Undocumented code entries AND stale doc entries each produce a
    BJX123 finding; a complete doc set is clean."""
    from blendjax.analysis.contracts import check_contracts
    from blendjax.analysis.core import parse_paths
    from blendjax.analysis.project import (
        NON_SIDECAR_KEYS,
        SIDECAR_LITERAL_KEYS,
    )

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(textwrap.dedent("""
        import os

        def emit(metrics):
            metrics.count("wire.frames")
            os.environ.get("BLENDJAX_MY_KNOB")
    """))
    docs = tmp_path / "docs"
    docs.mkdir()
    universe = "\n".join(
        f"- `{k}`" for k in sorted(SIDECAR_LITERAL_KEYS | NON_SIDECAR_KEYS)
    )
    (docs / "wire-protocol.md").write_text(universe + "\n")
    (docs / "observability.md").write_text("`wire.bytes` only.\n")
    modules, errors = parse_paths([str(pkg)], root=str(tmp_path))
    assert not errors
    got = check_contracts(modules, str(tmp_path))
    idents = {f.identity for f in got}
    assert "metric:wire.frames" in idents        # emitted, undocumented
    assert "stale-metric:wire.bytes" in idents   # documented, never emitted
    assert "knob:BLENDJAX_MY_KNOB" in idents
    assert all(f.rule == "BJX123" for f in got)

    (docs / "observability.md").write_text("`wire.frames` counted.\n")
    (docs / "knobs.md").write_text("`BLENDJAX_MY_KNOB` toggles it.\n")
    assert check_contracts(modules, str(tmp_path)) == []


def test_cli_contracts_gate_repo_is_clean():
    """The acceptance criterion: the real repo's catalogs and docs
    agree — `--contracts` exits 0 (and stays inside the CI budget)."""
    r = run_cli(["--contracts", "--max-seconds", "60"], cwd=REPO_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_contracts_exit_1_on_drift(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "def f(metrics):\n    metrics.count('ghost.metric')\n"
    )
    r = run_cli(["--contracts", "pkg"], cwd=str(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "BJX123" in r.stdout and "ghost.metric" in r.stdout


# -- suppression hygiene (BJX124) --------------------------------------------


def test_strict_suppressions_justification_shapes():
    from blendjax.analysis.core import check_suppression_hygiene

    got = check_suppression_hygiene(_mods(("pkg/m.py", """
        x = 1  # bjx: ignore[BJX101]
        y = 2  # bjx: ignore[BJX101] — sanctioned: init-time only
        # the reservoir is thread-confined here
        z = 3  # bjx: ignore[BJX117]
        # bjx: ignore[BJX108]
        w = 4
        msg = "suppress with '# bjx: ignore[BJX107]' and say why"
    """)))
    assert [f.line for f in got] == [2, 6]  # bare inline + bare above-line
    assert all(f.rule == "BJX124" for f in got)
    # markers inside string literals are prose, not suppressions
    assert all("BJX107" not in str(f.line) or f.line != 8 for f in got)


def test_strict_suppressions_identity_survives_line_shift():
    from blendjax.analysis.core import check_suppression_hygiene

    src = "x = 1  # bjx: ignore[BJX101]\n"
    a = check_suppression_hygiene(_mods(("pkg/m.py", src)))
    b = check_suppression_hygiene(_mods(("pkg/m.py", "# pad\n\n" + src)))
    assert len(a) == len(b) == 1
    assert a[0].identity == b[0].identity


def test_cli_strict_suppressions_flag(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text("x = 1  # bjx: ignore[BJX101]\n")
    off = run_cli(["pkg", "--no-baseline"], cwd=str(tmp_path))
    assert off.returncode == 0, off.stdout + off.stderr
    on = run_cli(
        ["pkg", "--no-baseline", "--strict-suppressions"], cwd=str(tmp_path)
    )
    assert on.returncode == 1, on.stdout + on.stderr
    assert "BJX124" in on.stdout


def test_repo_suppressions_all_justified():
    """Self-gate for the hygiene pass: every '# bjx: ignore[...]' in
    the repo carries its reason (CI runs with --strict-suppressions)."""
    from blendjax.analysis.core import check_suppression_hygiene, parse_paths

    paths = [os.path.join(REPO_ROOT, p) for p in ("blendjax", "scripts")]
    modules, errors = parse_paths(paths, root=REPO_ROOT)
    assert not errors
    got = check_suppression_hygiene(modules)
    assert got == [], [f.render() for f in got]


# -- SARIF output -------------------------------------------------------------


def test_cli_sarif_output_carries_identity_fingerprint():
    r = run_cli(
        [
            os.path.join("tests", "fixtures", "stamp_leak_trace.py"),
            "--no-baseline", "--format", "sarif",
        ],
        cwd=REPO_ROOT,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "bjx-lint"
    rule_ids = {rule["id"] for rule in run["tool"]["driver"]["rules"]}
    results = run["results"]
    assert any(res["ruleId"] == "BJX120" for res in results)
    assert all(res["ruleId"] in rule_ids for res in results)
    leak = next(res for res in results if res["ruleId"] == "BJX120")
    loc = leak["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("stamp_leak_trace.py")
    assert loc["region"]["startLine"] > 0
    assert (
        leak["partialFingerprints"]["bjxIdentity/v2"]
        == "tests.fixtures.stamp_leak_trace.feed:_trace"
        "->jax.jit(train_step)"
    )


def test_cli_full_repo_lint_within_budget():
    """The CI latency gate: the whole-program pass over the full repo
    (rules + dataflow + hygiene) completes inside --max-seconds 60."""
    r = run_cli(
        ["blendjax", "--strict-suppressions", "--max-seconds", "60"],
        cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr


# -- BJX126 mesh-axis-literal -------------------------------------------------

AXIS_LITERAL = """
    from jax.sharding import NamedSharding, PartitionSpec as P

    def pin(mesh, x):
        import jax
        return jax.device_put(x, NamedSharding(mesh, P("data")))

    def fold(mesh):
        return P(("data", "fsdp"), None)
"""


def test_bjx126_flags_axis_literals_in_library_code():
    got = findings(
        AXIS_LITERAL, relpath="blendjax/train/foo.py", select=["BJX126"]
    )
    assert [f.rule for f in got] == ["BJX126"] * 2
    assert "fsdp" in got[1].message


def test_bjx126_layout_layer_and_tests_are_exempt():
    assert rule_ids(
        AXIS_LITERAL, relpath="blendjax/parallel/foo.py",
        select=["BJX126"],
    ) == []
    assert rule_ids(
        AXIS_LITERAL, relpath="tests/test_foo.py", select=["BJX126"]
    ) == []


def test_bjx126_negatives_threaded_axis_and_non_axis_strings():
    clean = """
        from jax.sharding import PartitionSpec as P

        def pin(mesh, data_axis):
            return P(data_axis)

        def not_an_axis():
            return P("batch")

        def not_a_spec():
            return dict(axis="data")
    """
    assert rule_ids(
        clean, relpath="blendjax/train/foo.py", select=["BJX126"]
    ) == []


def test_bjx126_inline_suppression():
    src = """
        from jax.sharding import PartitionSpec as P

        def fixture(mesh):
            return P("data")  # bjx: ignore[BJX126]
    """
    assert rule_ids(
        src, relpath="blendjax/train/foo.py", select=["BJX126"]
    ) == []
