"""Records the fixture ``v5e_cube_step_scoped_x3.xplane.pb.gz``: three
synchronous executions of the ``cube_replay`` candidate's fused step on
one TPU v5e, under the profiler. Not a test and not a measurement; run
it on the chip when the step's name stacks change:

    chiprun --chips 1 -- python3 benchmark/tests/record_v5e_step.py

It writes ``chiprun_out/v5e_cube_step_scoped_x3.xplane.pb.gz`` (copy it
beside this file) and prints what ``trace_scopes`` makes of it. The
executions are synchronous so that the trace is small (one whole
execution between two that its start and stop may cut), the window
annotation covers all three, and the host's wait is ``bench.wait`` as
in PR 22's ``v5e_cube_step_x3.xplane.pb.gz``.
"""

import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import cells  # noqa: E402
import reduce_trace  # noqa: E402
import trace_scopes  # noqa: E402

NAME = "v5e_cube_step_scoped_x3.xplane.pb.gz"
SEED, MESSAGES = 1, 16


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs the chip: nothing was recorded", file=sys.stderr)
        return 3
    cell = cells.Cell(
        "cube_replay",
        benchmark_json=os.path.join(BENCH, "candidates", "cube_cells.json"),
    )
    recording = cell.ensure_recording(SEED, MESSAGES)
    state = cell.make_state(cell.model(), SEED)
    step = cell.make_step(state)
    with cell.pipeline(recording, loop=True) as pipe:
        it = iter(pipe)
        for _ in range(cells.WARMUP_STEPS):  # both compiles, outside the trace
            state, m = step(state, next(it))
            jax.block_until_ready(m)
        batches = [next(it) for _ in range(3)]
        trace_dir = os.path.join(cells.OUT, "traces", "record_v5e_step")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            for batch in batches:
                state, m = step(state, batch)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(m)
        jax.profiler.stop_trace()
    path = reduce_trace.find_xplane(trace_dir)
    out = os.path.join(ROOT, "chiprun_out", NAME)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(path, "rb") as src, gzip.open(out, "wb", 9) as dst:
        shutil.copyfileobj(src, dst)
    scopes = trace_scopes.by_scope(path)
    print(json.dumps({
        "file": out, "bytes": os.path.getsize(out),
        "executions": scopes["executions"],
        "ms_per_execution": {
            p: 1e3 * trace_scopes.seconds_of(scopes, [p])
            for p in trace_scopes.PARTS
        },
        "all_ms": 1e3 * sum(scopes["seconds"].values()),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
