"""Device time by the part of the step that spent it, read from the
trace's own operation metadata.

A profiler trace of a TPU says, for every device operation, where in
the program it came from: the event's *metadata* carries the stat
``tf_op``, the JAX name stack the operation was traced under
(``jit(_fused)/decode/vmap(vmap(palette_expand))/gather:``,
``jit(_fused)/while/body/closed_call/transpose(jvp(StreamFormer))/...``),
beside ``hlo_category``, ``bytes_accessed``, ``flops`` and
``program_id``. ``jax.profiler.ProfileData`` does not surface the stats
of event metadata, so :func:`op_metadata` walks the raw ``.xplane.pb``
(tsl/profiler/protobuf/xplane.proto; ``tests/xplane_writer.py`` in
reverse, no TensorFlow import):

    XSpace         { repeated XPlane planes = 1; }
    XPlane         { string name = 2;
                     map<int64, XEventMetadata> event_metadata = 4;
                     map<int64, XStatMetadata> stat_metadata = 5; }
    XEventMetadata { int64 id = 1; string name = 2;
                     string display_name = 4; repeated XStat stats = 5; }
    XStatMetadata  { int64 id = 1; string name = 2; }
    XStat          { int64 metadata_id = 1; double double_value = 2;
                     uint64 uint64_value = 3; int64 int64_value = 4;
                     string str_value = 5; bytes bytes_value = 6;
                     uint64 ref_value = 7; }   // 7 names a stat_metadata

Event names are ``ProfileData``'s ``e.name``, so times (``reduce_trace``)
and metadata join by name.

The program names its parts where they run (``blendjax.utils.metrics``
``STEP_SCOPES``, ``docs/observability.md``): ``jax.named_scope`` puts a
name into the path of every operation traced under it. Forward and
backward need no scope: flax and ``jax.grad`` already write
``jvp(<Model>)`` and ``transpose(jvp(<Model>))``. :func:`part_of` puts
each path into exactly one of :data:`PARTS`; :func:`by_scope` gives the
device self time of each path per *whole* execution of the step program
(decode runs once at the head of a dispatch: a slice with two cut
executions would miscount its share by up to a third).
"""

from __future__ import annotations

import glob
import os
import re
import struct
import time

import cells
import reduce_trace

STATS = ("tf_op", "hlo_category", "bytes_accessed", "flops", "program_id")

# The parts of a step, in order of precedence: an operation belongs to
# the first whose rule its path meets, and to REST if none does. The
# optimizer sits inside no differentiation, decode and reshard run
# before the scan; the order only decides operations that a later PR
# might trace under two names at once.
DECODE, RESHARD, FORWARD, BACKWARD, OPTIMIZER, REST = PARTS = (
    "decode", "reshard", "forward", "backward", "optimizer", "rest",
)
# Names inside the parts (``blendjax.utils.metrics`` STEP_SCOPES and
# KERNEL_NAMES) that the command-line report lists beside them.
INSIDE = (
    "palette_expand", "tile_decode_spatial", "tile_decode_scatter",
    "attn_core",
)


# -- the raw container ----------------------------------------------------------


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """``(field number, wire type, value)`` of one serialized message:
    an int for a varint, a memoryview for a length-delimited field, raw
    bytes for the two fixed widths."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = bytes(buf[i:i + width])
            i += width
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value message of one ``map<int64, Message>`` entry."""
    for field, wire, value in _fields(entry):
        if field == 2 and wire == 2:
            return value
    return b""


def _stat(stat, stat_names: dict):
    """``(stat name, value)`` of one XStat."""
    name, out = None, None
    for field, wire, value in _fields(stat):
        if field == 1:
            name = stat_names.get(value)
        elif field == 2:
            out = struct.unpack("<d", value)[0]
        elif field == 3:
            out = value
        elif field == 4:  # int64: two's complement in 64 bits
            out = value - (1 << 64) if value >> 63 else value
        elif field in (5, 6):
            out = _text(value)
        elif field == 7:
            out = stat_names.get(value)
    return name, out


def op_metadata(path: str) -> dict:
    """``{device plane: {event name: {"tf_op", "hlo_category",
    "bytes_accessed", "flops", "program_id"}}}`` from the planes' event
    metadata; a stat an operation does not carry is ``None``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for pf, pw, value in _fields(plane):
            if pf == 2 and pw == 2:
                name = _text(value)
            elif pf == 4 and pw == 2:
                events.append(_map_value(value))
            elif pf == 5 and pw == 2:
                sid, sname = 0, ""
                for sf, _sw, sv in _fields(_map_value(value)):
                    if sf == 1:
                        sid = sv
                    elif sf == 2:
                        sname = _text(sv)
                stat_names[sid] = sname
        if not reduce_trace.DEVICE_PLANE.match(name):
            continue
        ops = out.setdefault(name, {})
        for meta in events:
            event_name, stats = "", dict.fromkeys(STATS)
            for mf, mw, value in _fields(meta):
                if mf == 2 and mw == 2:
                    event_name = _text(value)
                elif mf == 5 and mw == 2:
                    key, stat_value = _stat(value, stat_names)
                    if key in stats:
                        stats[key] = stat_value
            ops[event_name] = stats
    return out


# -- paths and parts -------------------------------------------------------------


def segments(tf_op: str) -> list:
    """The name stack of an operation: ``a/b(c)/d:`` -> ``[a, b(c), d]``
    (the trailing colon closes the primitive's own name)."""
    return [s for s in (tf_op or "").rstrip(":").split("/") if s]


def has_scope(tf_op: str, name: str) -> bool:
    """Whether ``name`` is on the stack as a whole identifier. JAX wraps
    the first scope entered inside a transform in the transform's name
    (``vmap(vmap(palette_expand))``) and writes deeper ones as plain
    segments, so the name is looked for anywhere in a segment, never as
    part of a longer identifier (``decode`` is not in ``tile_decode``)."""
    pattern = re.compile(rf"(?<![A-Za-z0-9_]){re.escape(name)}(?![A-Za-z0-9_])")
    return any(pattern.search(s) for s in segments(tf_op))


def is_backward(tf_op: str) -> bool:
    return any(s.startswith("transpose(") for s in segments(tf_op))


def is_forward(tf_op: str) -> bool:
    return not is_backward(tf_op) and any(
        s.startswith("jvp(") for s in segments(tf_op)
    )


def part_of(tf_op: str) -> str:
    """The one part of the step an operation belongs to."""
    if has_scope(tf_op, DECODE):
        return DECODE
    if has_scope(tf_op, RESHARD):
        return RESHARD
    if is_backward(tf_op):
        return BACKWARD
    if is_forward(tf_op):
        return FORWARD
    if has_scope(tf_op, OPTIMIZER):
        return OPTIMIZER
    return REST


def matches(tf_op: str, include, exclude=()) -> bool:
    """``include``/``exclude`` name parts (:data:`PARTS`) or scopes."""
    def meets(word):
        return part_of(tf_op) == word if word in PARTS else has_scope(
            tf_op, word
        )

    return any(map(meets, include)) and not any(map(meets, exclude))


# -- the reduction ---------------------------------------------------------------


def whole_executions(lines: dict, lo: float, hi: float) -> list:
    """``[(start, end)]`` of the step program's executions the trace
    holds whole: the ``XLA Modules`` events of the module that took most
    of the slice, the line's first and last events dropped (the trace's
    own start and stop cut them and they look complete), inside the
    window. The rule of ``reduce_trace.summarize``."""
    runs = sorted(
        lines.get(reduce_trace.MODULES_LINE, ()), key=lambda ev: ev[1]
    )
    whole: dict = {}
    for name, s, e in runs[1:-1]:
        if s >= lo and e <= hi:
            whole.setdefault(name, []).append((s, e))
    if not whole:
        return []
    return max(whole.values(), key=lambda v: sum(e - s for s, e in v))


def by_scope(path: str):
    """``{"executions", "devices", "seconds": {tf_op: self seconds per
    execution}, "ops": {event name: (self seconds per execution, its
    metadata)}}`` over the operations inside whole executions of the
    step program, mean over devices; operations without a ``tf_op`` are
    keyed ``""``. ``None`` where the trace holds no whole execution."""
    planes = reduce_trace.read_planes(path)
    metadata = op_metadata(path)
    device_events = {
        name: lines[reduce_trace.OPS_LINE]
        for name, lines in planes.items()
        if reduce_trace.DEVICE_PLANE.match(name)
        and lines.get(reduce_trace.OPS_LINE)
    }
    if not device_events:
        return None
    lo, hi = reduce_trace._window(planes, device_events)
    ops: dict = {}
    executions = devices = 0
    for name, events in device_events.items():
        runs = whole_executions(planes[name], lo, hi)
        if not runs:
            continue
        devices += 1
        executions += len(runs)
        inside = [
            ev for ev in events
            if any(s <= ev[1] and ev[2] <= e for s, e in runs)
        ]
        for op, ns, _leaf, _s, _e in reduce_trace.self_times(inside):
            had = ops.get(op) or (0.0, metadata.get(name, {}).get(op) or {})
            ops[op] = (had[0] + ns / 1e9 / len(runs), had[1])
    if not devices:
        return None
    ops = {op: (s / devices, meta) for op, (s, meta) in ops.items()}
    seconds: dict = {}
    for s, meta in ops.values():
        key = meta.get("tf_op") or ""
        seconds[key] = seconds.get(key, 0.0) + s
    return {
        "executions": executions // devices, "devices": devices,
        "seconds": seconds, "ops": ops,
    }


def seconds_of(scopes: dict, include, exclude=()) -> float:
    """Self seconds per execution of the paths that :func:`matches`."""
    return sum(
        s for tf_op, s in scopes["seconds"].items()
        if matches(tf_op, include, exclude)
    )


# -- the program's spans on the profiler's clock -----------------------------------

PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")


def host_spans(path: str) -> list:
    """``[(span name, start_ns, end_ns)]`` of the program's own spans
    (``Metrics.span`` opens a ``TraceAnnotation`` of the same name) on
    the host plane, in time order: dotted lower-case names that are not
    the harness's ``bench.*``. No metric reads them yet: they are for
    the operator's Perfetto view, and for gap attribution once a cell
    idles."""
    return sorted(
        (
            ev for name, lines in reduce_trace.read_planes(path).items()
            if not reduce_trace.DEVICE_PLANE.match(name)
            for events in lines.values() for ev in events
            if PROGRAM_SPAN.match(ev[0])
            and not ev[0].startswith(reduce_trace.ANNOTATION_PREFIX)
        ),
        key=lambda ev: ev[1],
    )


# -- this run's trace --------------------------------------------------------------

_parsed: dict = {}


def newest_trace(since: float = 0.0):
    """The newest ``.xplane.pb`` under ``out/traces/*/`` written after
    ``since`` (seconds since the epoch), or ``None``. ``run.py`` clears
    and writes its cell's directory there just before the readers run."""
    found = [
        p for p in glob.glob(os.path.join(
            cells.OUT, "traces", "*", "plugins", "profile", "*", "*.xplane.pb"
        ))
        if os.path.getmtime(p) >= since
    ]
    return max(found, key=os.path.getmtime) if found else None


def this_run(obs):
    """:func:`by_scope` of the run's own trace, parsed once for all the
    readers; ``None`` where the run was not traced."""
    if not obs.get("trace"):
        return None
    # the trace is of the window's last steps: nothing older is this run's
    window_began = time.time() - (time.monotonic() - obs["window"]["t0_mono"])
    path = newest_trace(since=window_began)
    if path is None:
        return None
    if path not in _parsed:
        _parsed[path] = by_scope(path)
    return _parsed[path]


def main(argv=None) -> int:
    """``python3 benchmark/trace_scopes.py TRACE.xplane.pb [--chunk N]
    [--top K]``: the step by part, ms per update, with the operations
    that take most of each part and of the scopes inside the parts."""
    import argparse
    import json

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("trace")
    ap.add_argument("--chunk", type=int, default=1, help="updates per dispatch")
    ap.add_argument("--top", type=int, default=5)
    args = ap.parse_args(argv)
    scopes = by_scope(args.trace)
    if scopes is None:
        print("the trace holds no whole execution of a program")
        return 1
    per_update = 1e3 / args.chunk

    def table(word) -> dict:
        rows = sorted(
            ((s, op, meta) for op, (s, meta) in scopes["ops"].items()
             if matches(meta.get("tf_op"), [word])),
            key=lambda r: -r[0],
        )
        return {
            "ms_per_update": per_update * sum(r[0] for r in rows),
            "operations": len(rows),
            "top": [
                {"ms_per_update": per_update * s, "op": op.split(" = ")[0],
                 "result": op.partition(" = ")[2].split(" ")[0][:48], **meta}
                for s, op, meta in rows[:args.top]
            ],
        }

    inside = {w: table(w) for w in INSIDE}
    print(json.dumps({
        "executions": scopes["executions"], "devices": scopes["devices"],
        "ms_per_update": per_update * sum(scopes["seconds"].values()),
        "parts": {p: table(p) for p in PARTS},
        "scopes": {w: t for w, t in inside.items() if t["operations"]},
    }, indent=1))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
