"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports about the device: busy and idle time, the time of each device
operation, collective time not hidden behind compute, and what the host
was doing during the idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. What a trace
of a TPU holds (looked at by hand, PR 22): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` carries one event per
executed HLO operation (nested: a ``while`` spans the operations of its
body) and whose line ``XLA Modules`` carries one per executed program
(an execution under way when the trace starts or stops is cut there and
looks like a short whole one), and one ``/host:CPU`` plane with a line per host thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.
The harness annotates its own calls (``bench.next``, ``bench.submit``,
``bench.drain``) and the traced slice itself (``bench.trace_window``);
host and device events share the profiler's clock.

Definitions:

- *busy*: the union of the intervals in which an operation ran on the
  device, clipped to the window; *idle share* = 1 - busy / window.
- *self time* of an operation: its duration minus what operations
  nested inside it cover, so a ``while`` does not count its body twice.
- *collective, exposed*: time covered by collective operations that
  have nothing nested in them and by no other such leaf operation of
  that device. On the ``XLA Ops`` line an asynchronous collective is a
  short ``-start`` and a ``-done`` that waits: the wait is what shows.
- an *idle gap* is a maximal interval of the window with no operation
  on the device; it is attributed to the harness annotation that
  overlaps most of it (``unannotated`` if none does).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"  # one event per executed program
BETWEEN_OPS_NS = 20_000.0  # shorter gaps are the device's own, not the host's
WINDOW_ANNOTATION = "bench.trace_window"
ANNOTATION_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def read_planes(path: str) -> dict:
    """``{plane name: {line name: [(event name, start_ns, end_ns)]}}``."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns))
                for e in line.events
            )
    return planes


def union(intervals) -> list:
    """Sorted, merged ``[(start, end)]``."""
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """The part of merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events) -> list:
    """``[(name, self_ns, is_leaf, start, end)]`` for the nested events
    of one line: an event's self time excludes what its children cover."""
    out = []
    stack: list = []  # [name, start, end, covered_by_children, has_child]

    def close(item):
        name, s, e, covered, has_child = item
        out.append((name, max(e - s - covered, 0.0), not has_child, s, e))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            parent[3] += min(e, parent[2]) - s
            parent[4] = True
        stack.append([name, s, e, 0.0, False])
    while stack:
        close(stack.pop())
    return out


def _window(planes: dict, device_events: dict):
    for lines in (v for k, v in planes.items() if not DEVICE_PLANE.match(k)):
        for events in lines.values():
            spans = [(s, e) for n, s, e in events if n == WINDOW_ANNOTATION]
            if spans:
                return min(s for s, _ in spans), max(e for _, e in spans)
    every = [ev for evs in device_events.values() for ev in evs]
    if not every:
        raise ValueError("the trace holds no device operation")
    return min(s for _, s, _ in every), max(e for _, _, e in every)


def summarize(planes: dict, top: int = 10) -> dict:
    """The reduction. Times in seconds; ``devices`` in device order."""
    device_lines = {
        int(DEVICE_PLANE.match(name).group(2)): lines
        for name, lines in planes.items()
        if DEVICE_PLANE.match(name) and lines.get(OPS_LINE)
    }
    device_events = {k: v[OPS_LINE] for k, v in device_lines.items()}
    if not device_events:
        raise ValueError(
            f"no {OPS_LINE!r} line on any device plane: {sorted(planes)}"
        )
    lo, hi = _window(planes, device_events)
    annotations = [
        (n, s, e)
        for name, lines in planes.items() if not DEVICE_PLANE.match(name)
        for events in lines.values() for n, s, e in events
        if n.startswith(ANNOTATION_PREFIX) and n != WINDOW_ANNOTATION
    ]
    devices = []
    op_time: dict = {}
    gap_time: dict = {}
    module_runs: dict = {}  # name -> [(seconds, busy seconds)] of whole runs
    for dev in sorted(device_events):
        events = [
            (n, max(s, lo), min(e, hi)) for n, s, e in device_events[dev]
            if min(e, hi) > max(s, lo)
        ]
        busy = union((s, e) for _, s, e in events)
        selfs = self_times(events)
        for name, ns, _leaf, _s, _e in selfs:
            op_time[name] = op_time.get(name, 0.0) + ns
        coll = union(
            (s, e) for n, _ns, leaf, s, e in selfs
            if leaf and COLLECTIVE.match(n)
        )
        compute = union(
            (s, e) for n, _ns, leaf, s, e in selfs
            if leaf and not COLLECTIVE.match(n)
        )
        # Programs run on this device. Only an execution the trace holds
        # whole counts: the first and the last event of the line may be
        # cut by the trace's own start and stop, and look complete.
        runs = sorted(
            device_lines[dev].get(MODULES_LINE, ()), key=lambda ev: ev[1]
        )
        for n, s, e in runs[1:-1]:
            if s >= lo and e <= hi:
                module_runs.setdefault(n, []).append(
                    ((e - s) / 1e9, total(clip(busy, s, e)) / 1e9)
                )
        gaps = subtract([(lo, hi)], busy)
        for gs, ge in gaps:
            best, best_overlap = "unannotated", 0.0
            if ge - gs < BETWEEN_OPS_NS:
                gap_time["between_ops"] = (
                    gap_time.get("between_ops", 0.0) + (ge - gs)
                )
                continue
            for n, s, e in annotations:
                overlap = min(e, ge) - max(s, gs)
                if overlap > best_overlap:
                    best, best_overlap = n, overlap
            gap_time[best] = gap_time.get(best, 0.0) + (ge - gs)
        devices.append({
            "device": dev,
            "busy_s": total(busy) / 1e9,
            "idle_share": 1.0 - total(busy) / (hi - lo),
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(subtract(coll, compute)) / 1e9,
            "longest_gap_s": max((e - s for s, e in gaps), default=0.0) / 1e9,
            "ops": len(events),
        })
    n = len(devices)

    def ranked(table: dict) -> list:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / 1e9 / n] for name, ns in rows]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "devices": devices,
        # programs the trace holds whole executions of, all devices
        "modules": {
            name: {
                "executions": len(runs),
                "seconds_per_execution": sum(r[0] for r in runs) / len(runs),
                "busy_s_per_execution": sum(r[1] for r in runs) / len(runs),
            }
            for name, runs in module_runs.items()
        },
        "device_ops": ranked(op_time),   # self time, mean over devices
        "idle_gaps": ranked(gap_time),   # by what the host was doing
    }


def reduce_trace(trace_dir: str) -> dict:
    return summarize(read_planes(find_xplane(trace_dir)))
