"""One cell of BENCHMARK.json, once.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

A new process that takes the cell's chips, sets up (producers or the
recording, the plain reference from the seeded parameters, state on the
device from ``--seed``, production's first dispatch against the
reference, warm-up of the cell's one program), measures for ``--seconds``
and prints one JSON object as the last line of its standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, when
traced ``breakdown``, and last ``compared``: each number that was compared
beside its limit, which are also the last lines on standard error. With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the profiler is on for a short steady slice at the end of
the window (its ``stop_trace`` falls outside) and the metrics are the
cell's per-layer metrics. Everything else (the checks one by one, the
doctor's verdict, sample counts, cache hits) goes on earlier lines and, in
full, into ``benchmark/out/runs/``.

It never falls back to the CPU: without a TPU, or with fewer chips than
the cell asks for, it prints its reason on stderr, no result, and exits
3. ``--rehearse`` is the one exception: the cell's tiny ``rehearse``
size on whatever backend is there, ``"rehearsal": true`` in the line,
counts only and no device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:  # the package is not installed; children get
        sys.path.insert(0, _p)  # the root from the launcher's PYTHONPATH

import cells  # noqa: E402
import stats  # noqa: E402

EXIT_NO_DEVICE = 3
# The traced slice: the profiler is on for the last part of the window,
# at least this many driver steps and seconds.
TRACE_MIN_STEPS = 3
TRACE_MIN_SECONDS = 2.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


class Check:
    """The named conditions ``correct`` is the conjunction of."""

    def __init__(self):
        self.results: dict = {}

    def __call__(self, name: str, ok, detail=None) -> None:
        self.results[name] = {"ok": bool(ok), "detail": detail}

    @property
    def ok(self) -> bool:
        return bool(self.results) and all(r["ok"] for r in self.results.values())

    def failures(self) -> dict:
        return {k: v for k, v in self.results.items() if not v["ok"]}


def recording_step(step, sink: list):
    """``step`` with every dispatch's vector of per-update losses kept
    (device arrays, fetched after the drain); the driver itself only
    ever fetches the last of some."""

    def recorded(state, batch):
        state, m = step(state, batch)
        sink.append(m["loss"])
        return state, m

    recorded._cache_size = step._cache_size  # keeps the retrace audit on
    return recorded


def producers_seen() -> dict:
    """``{btid: last sequence number}`` and the native-code telemetry
    of each producer, from the program's lineage registry."""
    from blendjax.obs.lineage import lineage

    return {
        btid: {
            "last_seq": entry.get("last_seq"),
            "counters": entry.get("telemetry", {}).get("counters", {}),
        }
        for btid, entry in lineage.report().items()
    }


def drain_tracer(into: list) -> None:
    """The collector keeps 256 records: take them out as the run goes.
    Records complete on this thread only (the driver retires here)."""
    from blendjax.obs.trace import tracer

    recs = tracer.records()
    if recs:
        into.extend(recs)
        tracer.reset()


def short_op(name: str, limit: int = 96) -> str:
    """A device operation as the trace prints it is its whole HLO line;
    keep its name, its result and its kind."""
    lhs, _, rhs = name.partition(" = ")
    kind = rhs.split("kind=")[1].split(",")[0] if "kind=" in rhs else ""
    target = (
        rhs.split('custom_call_target="')[1].split('"')[0]
        if "custom_call_target=" in rhs else ""
    )
    return " ".join(
        x for x in (lhs, rhs.split(" ")[0][:48], kind, target) if x
    )[:limit]


def memory_peak_bytes(devices):
    """Peak occupancy of the fullest chip: the peak of live buffers plus
    what the runtime holds reserved for the programs' temporaries. On
    the v5e the fused step's temporaries (9.7 GB for vit_b16) are a
    standing reservation that ``peak_bytes_in_use`` does not count."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use"):
            peaks.append(
                stats["peak_bytes_in_use"]
                + stats.get("peak_bytes_reserved", 0)
            )
    return max(peaks) if peaks else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument(
        "--benchmark-json", default=None,
        help="another file in BENCHMARK.json's format (cells not admitted "
        "yet, harness tests); the driver never passes it",
    )
    args = ap.parse_args(argv)
    cell = cells.Cell(args.workload, args.rehearse, args.benchmark_json)
    seconds = args.seconds if args.seconds is not None else float(
        cell.benchmark["run_seconds"]
    )
    live = cell.traffic["kind"] == "live"
    marks: dict = {}  # seconds since the process started, for the detail file

    def mark(name: str) -> None:
        marks[name] = round(time.perf_counter() - T_PROCESS_START, 3)

    with contextlib.ExitStack() as stack:
        # producers first: they come up while JAX is imported
        launcher = stack.enter_context(cell.launcher(args.seed)) if live else None

        import jax
        import numpy as np

        devices = jax.devices()
        mark("devices")
        platform = devices[0].platform
        if not args.rehearse and (
            platform == "cpu" or len(devices) < cell.chips
        ):
            print(
                f"{cell.name} needs {cell.chips} accelerator chip(s); JAX "
                f"found {len(devices)} x {platform}: nothing was run",
                file=sys.stderr,
            )
            return EXIT_NO_DEVICE
        devices = devices[: cell.chips]
        device = {
            "platform": platform, "kind": devices[0].device_kind,
            "count": len(devices),
        }

        import flops
        import reference
        from blendjax.train import configure_compilation_cache
        from blendjax.utils.metrics import metrics

        if not args.rehearse:
            flops.peak(device["kind"])  # a chip without a peak on record is an error
        cache_dir = configure_compilation_cache()
        compiles = {"n": 0, "seconds": 0.0, "hits": 0, "misses": 0}

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                compiles["n"] += 1
                compiles["seconds"] += duration

        def on_event(event, **_):
            if event.startswith("/jax/compilation_cache/cache_"):
                compiles[event.rsplit("_", 1)[1]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        say({
            "phase": "start", "workload": cell.name, "seed": args.seed,
            "rehearsal": args.rehearse, "device": device,
            "cpu_count": os.cpu_count(), "jax": jax.__version__,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries": len(os.listdir(cache_dir)),
        })
        check = Check()
        mesh = cell.mesh(devices)
        chunk, batch_images = cell.chunk, cell.batch
        check_messages = int(cell.traffic["reference_messages"])
        n_messages = int(cell.traffic.get("messages", check_messages))
        recording = cell.ensure_recording(args.seed, n_messages)
        mark("recording")

        # -- (1) the plain reference, first and alone ---------------------------
        # The seeded state is made, its moments dropped, and its parameters
        # consumed by the reference (20 bytes a parameter at its peak,
        # reference.reference_losses); then the same program makes the
        # production state from the same key, whose parameters must be
        # the ones the reference started from.
        # The model and the first call of the state's program (its load or
        # compile) stay on ``setup.compile_s``'s clock, where they always
        # were; ``reference_s`` is the stage's own time.
        t_state = time.perf_counter()
        model = cell.model()
        ref_cfg = cell.config["reference_check"]
        updates = min(int(ref_cfg["updates"]), check_messages, chunk)
        make_state, key = cell.state_fn(model, mesh), jax.random.key(args.seed)
        state = jax.block_until_ready(make_state(key))
        first_state_s = time.perf_counter() - t_state
        mark("seeded_state")
        t_ref = time.perf_counter()
        seeded_sum = reference.parameter_checksum(state.params)
        seeded = jax.device_put(state.params, devices[0])
        del state
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(seeded))
        stage = {"live_peak_bytes": 0}

        def watch():
            stage["live_peak_bytes"] = max(
                stage["live_peak_bytes"], reference.live_bytes()
            )

        ref_losses = reference.reference_losses(
            cells.load_module("references", cell.model_class()).forward,
            cell.config["model"]["kwargs"], cell.reference_loss(),
            cell.optimizer(), seeded,
            reference.decode_recording(recording, updates),
            int(ref_cfg["microbatch"]), watch=watch,
        )
        del seeded  # donated: nothing of the stage is left on the device
        stage.update(
            parameters=n_params,
            live_bytes_per_parameter=stage["live_peak_bytes"] / n_params,
            live_bytes_after=reference.live_bytes(),
            peak_bytes_in_use=(devices[0].memory_stats() or {}).get(
                "peak_bytes_in_use"
            ),
        )
        reference_s = time.perf_counter() - t_ref
        mark("reference")

        # -- state, step, driver, stream ---------------------------------------
        t_programs = time.perf_counter()
        state = make_state(key)
        differing = int(
            (reference.parameter_checksum(state.params) != seeded_sum).sum()
        )
        check("seeded_parameters", differing == 0, {
            "leaves": int(seeded_sum.size), "differing": differing,
        })
        step = cell.make_step(state, mesh)
        loss_vectors: list = []
        driver = cell.make_driver(
            recording_step(step, loss_vectors), state, mesh
        )
        if live:
            pipe = stack.enter_context(cell.pipeline(
                launcher.addresses["DATA"], mesh, launcher=launcher
            ))
        else:
            pipe = stack.enter_context(
                cell.pipeline(recording, mesh, loop=True)
            )
        it = iter(pipe)
        mark("state_and_stream")

        # -- production's first dispatch against the reference's losses --------
        if live:
            with cell.pipeline(recording, mesh) as once:
                check_batch = next(iter(once))
        else:
            check_batch = next(it)
        driver.submit(check_batch)
        driver.drain()
        verdict = reference.compare(
            np.asarray(loss_vectors[0], np.float32).reshape(-1), ref_losses,
            cell.config["precision"], ref_cfg.get("rtol"),
        )
        check("reference", verdict["ok"], {
            k: verdict[k] for k in ("updates", "max_rel_diff", "rtol")
        })
        say({
            "phase": "reference", "seconds": round(reference_s, 2), **verdict,
            "seeded_parameters": differing == 0, "stage": stage,
        })
        mark("first_dispatch")

        # -- warm-up: the cell's one (group length, shape) program. The
        # check above was its first dispatch; the donated step compiles
        # once more when it first sees its own output's layouts.
        pull0_mono = time.monotonic()  # from here the stream is pulled steadily
        for _ in range(cells.WARMUP_STEPS - 1):
            batch = next(it)
            driver.submit(batch)
        driver.drain()
        compile_s = first_state_s + time.perf_counter() - t_programs
        mark("warm")
        counters = metrics.report()["counters"]
        paths = sorted(
            k.rsplit(".", 1)[1] for k in counters
            if k.startswith("tiles.decode_path.")
        )
        on_tpu = platform == "tpu"
        want_paths = (
            ["xla_scatter"] if not on_tpu
            else ["pallas_spatial"] if mesh is None
            else ["pallas_spatial", "shard_map"]
        )
        check("decode_path", paths == want_paths, paths)
        lowered = cells.lower_fused(step, driver.state, batch).as_text()
        check(
            "decode_kernel_in_step", ("tpu_custom_call" in lowered) == on_tpu,
            "tpu_custom_call" in lowered,
        )
        del lowered
        warm = {
            "dispatches": driver.dispatches, "steps": driver.steps,
            "programs": step._cache_size(), "compiles": dict(compiles),
            "producers": producers_seen(),
        }
        frames: list = []
        drain_tracer(frames)
        frames.clear()
        metrics.reset()
        trace_dir = os.path.join(cells.OUT, "traces", cell.name)
        tracing = {"on": False, "done": not args.trace}
        window_span = None

        # -- the measured window ------------------------------------------------
        images_handed = 0
        retired0 = driver.images_retired
        t0_mono = time.monotonic()
        t0 = time.perf_counter()
        setup_s = t0 - T_PROCESS_START
        mark("window")
        while True:
            now = time.perf_counter() - t0
            sliced = tracing["on"] and (
                driver.steps - tracing["steps"] >= TRACE_MIN_STEPS
                and now - tracing["t"] >= TRACE_MIN_SECONDS
            )
            if now >= seconds and (tracing["done"] or sliced):
                break
            if not tracing["done"] and not tracing["on"]:
                # the slice is the END of the window, so that stop_trace
                # (seconds, with 200k device events) falls outside it
                per_step = now / max(driver.steps - warm["steps"], 1)
                if now >= seconds - max(
                    TRACE_MIN_SECONDS, (TRACE_MIN_STEPS + 0.5) * per_step
                ):
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    window_span = jax.profiler.TraceAnnotation(
                        "bench.trace_window"
                    )
                    window_span.__enter__()
                    tracing.update(
                        on=True, t=time.perf_counter() - t0, steps=driver.steps
                    )
            with jax.profiler.TraceAnnotation("bench.next"):
                batch = next(it)
            images_handed += int(batch["_packed"].shape[0]) * batch_images
            with jax.profiler.TraceAnnotation("bench.submit"):
                driver.submit(batch)
            drain_tracer(frames)
        if tracing["on"]:
            window_span.__exit__(None, None, None)
        with jax.profiler.TraceAnnotation("bench.drain"):
            final_loss = driver.drain()
        t1 = time.perf_counter()
        t1_mono = time.monotonic()
        if tracing["on"]:
            jax.profiler.stop_trace()
        drain_tracer(frames)
        elapsed = t1 - t0
        images = driver.images_retired - retired0

        # -- what the window showed ---------------------------------------------
        report = metrics.report()
        c, spans = report["counters"], report["spans"]
        doctor = pipe.doctor(driver).render()
        peak_bytes = memory_peak_bytes(devices)
        losses = np.concatenate([
            np.asarray(v, np.float32).reshape(-1) for v in loss_vectors
        ])
        window_losses = losses[(warm["steps"]) * chunk:]
        seen = producers_seen()
        published = sum(
            (p["last_seq"] or 0)
            - (warm["producers"].get(b, {}).get("last_seq") or 0)
            for b, p in seen.items()
        ) if live else None

        # (2) losses: all finite, and under the band after the budget
        band = cell.config["loss_band"]
        at = math.ceil(band["after_images"] / batch_images)
        check("finite_losses", bool(np.isfinite(losses).all()), int(losses.size))
        at_budget = (
            float(np.median(losses[at:at + chunk])) if len(losses) > at
            else None
        )
        check(
            "loss_band", at_budget is not None and at_budget < band["below"],
            {**band, "loss": at_budget},
        )
        # (3) the smoke's invariants, over the window
        steps = driver.steps - warm["steps"]
        check(
            "one_dispatch_per_step",
            driver.dispatches - warm["dispatches"] == steps > 0,
            {"dispatches": driver.dispatches - warm["dispatches"], "steps": steps},
        )
        check("no_seq_gaps", not c.get("wire.seq_gaps"), c.get("wire.seq_gaps", 0))
        check("no_aot_fallbacks", not c.get("train.aot_fallbacks"))
        check("no_standalone_decode", "decode.dispatch" not in spans)
        if live:
            from blendjax._native import native_status

            native = all(native_status().values()) and len(seen) == int(
                cell.traffic["producers"]
            ) and all(
                p["counters"].get("native.loaded", 0) >= 2
                and not p["counters"].get("native.fallbacks")
                for p in seen.values()
            )
            check("native_producers", native, {
                b: p["counters"] for b, p in seen.items()
            })
        # (4) nothing compiled inside the window
        check(
            "no_compile_in_window",
            compiles["n"] == warm["compiles"]["n"]
            and step._cache_size() == warm["programs"]
            and not c.get("device.retraces"),
            {"backend_compiles": compiles["n"] - warm["compiles"]["n"],
             "programs": [warm["programs"], step._cache_size()],
             "retraces": c.get("device.retraces", 0)},
        )
        attempted, failed = stats.attempted_failed(
            images_handed=images_handed, batch=batch_images,
            seq_gaps=c.get("wire.seq_gaps", 0),
            torn_messages=c.get("wire.shm_torn", 0),
            dropped_messages=c.get("tiles.degraded_groups", 0),
            losses=window_losses,
        )

        # -- metrics ---------------------------------------------------------------
        ages = stats.frame_ages_ms(
            frames, t0_mono, t1_mono, published_after=pull0_mono
        )
        trace_summary = None
        if args.trace and not args.rehearse:
            import reduce_trace

            trace_summary = reduce_trace.reduce_trace(trace_dir)
        device["memory_peak_bytes"] = peak_bytes
        if trace_summary:
            device["busy_s"] = trace_summary["busy_s"]
            device["window_s"] = trace_summary["window_s"]
        obs = {
            "window": {
                "seconds": elapsed, "images": images, "chips": cell.chips,
                "images_handed": images_handed, "batch": batch_images,
                "chunk": chunk, "updates": int(window_losses.size),
                "t0_mono": t0_mono, "t1_mono": t1_mono,
                "pull0_mono": pull0_mono,
            },
            "spans": spans, "counters": c, "frames": frames,
            "producers": {"messages_published": published},
            "trace": trace_summary,
            "device": device,
            "flops_per_image": flops.train_flops_per_image(cell),
            "model": {
                "class": cell.model_class(),
                "kwargs": cell.config["model"]["kwargs"],
                "input_shape": (*cell.shape, cell.channels),
                "batch_per_chip": batch_images // cell.chips,
                "precision": cell.config["precision"],
            },
            "setup": {
                "compile_s": compile_s
                + c.get("train.compile_ms", 0.0) / 1e3,
            },
        }
        end_to_end = {
            "img_per_s_per_chip": images / elapsed / cell.chips,
            "setup_s": setup_s,
        }
        readings: dict = {}
        if args.trace:
            for m in cell.metrics("per_layer"):
                spec = cells.load_json("layer_metrics", f"{m['name']}.json")
                reader = cells.load_module("readers", spec["reader"])
                readings[m["name"]] = reader.read(obs, **spec.get("args", {}))
        else:
            readings = {
                m["name"]: end_to_end.get(m["name"])
                for m in cell.metrics("end_to_end")
            }
        units = {
            m["name"]: m["unit"]
            for m in cell.benchmark["end_to_end"] + cell.benchmark["per_layer"]
        }
        out_metrics = {  # a reader that found nothing to read leaves it out
            name: {"value": float(value), "unit": units[name]}
            for name, value in readings.items()
            # a rehearsal prints counts only: no time, rate, share or
            # utilisation from a CPU run under a device metric's name
            if value is not None
            and (not args.rehearse or name == "wire.bytes_per_img")
        }

        detail = {
            "workload": cell.name, "seed": args.seed, "trace": args.trace,
            "rehearsal": args.rehearse, "seconds": elapsed,
            "images": images, "updates": int(window_losses.size),
            "driver_steps": steps, "driver": driver.stats,
            "first_loss": float(losses[0]), "final_loss": float(final_loss),
            "frame_age_samples": len(ages),
            "frame_age_p50_ms": stats.percentile(ages, 50) if ages else None,
            "pull0_before_window_s": round(t0_mono - pull0_mono, 3),
            # [seconds after the window began that it was published, age ms]
            "frames": [
                [round(st["publish"] - t0_mono, 3),
                 round((st["step_retire"] - st["publish"]) * 1e3, 1)]
                for st in map(stats.first_stamps, frames)
                if "publish" in st and "step_retire" in st
            ],
            "setup": {
                "setup_s": setup_s, "programs_s": compile_s,
                "first_state_s": first_state_s,
                "reference_s": reference_s, "compiles": warm["compiles"],
                "marks": marks,
            },
            "checks": check.results, "doctor": doctor,
            "counters": c,
            "spans": {k: {"count": v["count"], "total_s": v["total_s"]}
                      for k, v in spans.items()},
            "producers": seen, "messages_published": published,
            "end_to_end": end_to_end,
            "per_layer": readings if args.trace else None,
            "trace_summary": trace_summary,
        }
        runs = os.path.join(cells.OUT, "runs")
        os.makedirs(runs, exist_ok=True)
        with open(os.path.join(
            runs, f"{cell.name}-s{args.seed}-t{args.trace}.json"
        ), "w") as f:
            json.dump(detail, f, indent=1, default=str)
        say({
            "phase": "window", "seconds": round(elapsed, 3), "images": images,
            "updates": int(window_losses.size), "driver_steps": steps,
            "first_loss": float(losses[0]), "final_loss": float(final_loss),
            "frame_age_samples": len(ages), "cpu_count": os.cpu_count(),
            "messages_published": published,
            "setup": detail["setup"], "doctor": doctor,
            "failed_checks": check.failures(),
        })
        line = {
            "correct": check.ok, "attempted": attempted, "failed": failed,
            "metrics": out_metrics, "device": device,
        }
        if args.rehearse:
            line["rehearsal"] = True
        if trace_summary:
            line["breakdown"] = {
                "device_ops": [
                    [short_op(n), t] for n, t in trace_summary["device_ops"]
                ],
                "idle_gaps": trace_summary["idle_gaps"],
            }
        # every number that was compared beside its limit: last in the
        # line, and the last lines on standard error
        line["compared"] = {
            "loss_rel_diff": [verdict["max_rel_diff"], verdict["rtol"]],
            "seeded_leaves_differing": [differing, 0],
            "loss_at_budget": [at_budget, band["below"]],
            "nonfinite_losses": [int((~np.isfinite(losses)).sum()), 0],
            "failed_checks": [len(check.failures()), 0],
        }
    say(line)
    for name, result in check.failures().items():
        print(f"failed check {name}: {result['detail']}", file=sys.stderr)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    from blendjax.launcher.launcher import kill_all_spawned

    try:
        code = main()
    finally:
        kill_all_spawned()  # no child outlives the run, whatever raised
    sys.exit(code)
