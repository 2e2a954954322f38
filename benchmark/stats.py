"""The benchmark's own arithmetic: percentiles of a sample, and what
counts as attempted and failed."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values`` by linear
    interpolation between closest ranks (numpy's default), on the
    benchmark's own code so that no PR changes it from outside."""
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    if not 0 <= q <= 100:
        raise ValueError(f"q={q} outside 0..100")
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def first_stamps(record) -> dict:
    """``{stage: t_mono}`` of one of the tracer's records
    (``{"stages": [[name, t_mono, t_wall], ...]}``); the first stamp of
    a stage counts."""
    stamps: dict = {}
    for name, mono, _wall in record.get("stages", ()):
        stamps.setdefault(name, float(mono))
    return stamps


def frame_ages_ms(records, t0_mono: float, t1_mono: float,
                  first: str = "publish", last: str = "step_retire",
                  published_after: float = float("-inf")) -> list:
    """``first`` -> ``last`` in ms, on the monotonic clock (system-wide
    on one host), for every trace record that reached ``last`` inside
    the window ``[t0_mono, t1_mono]`` and was published at or after
    ``published_after``.

    ``published_after`` is the moment the consumer began to pull the
    stream steadily. Frames published before it sat in full queues all
    through set-up, and their age would measure set-up, not the system;
    a frame published after it joins the tail of full queues in front of
    a consumer at its steady pace, which is the steady state."""
    ages = []
    for stamps in map(first_stamps, records):
        if first in stamps and last in stamps and (
            t0_mono <= stamps[last] <= t1_mono
            and stamps.get("publish", published_after) >= published_after
        ):
            ages.append((stamps[last] - stamps[first]) * 1e3)
    return ages


def attempted_failed(*, images_handed: int, batch: int, seq_gaps: int,
                     torn_messages: int, dropped_messages: int,
                     losses) -> tuple:
    """``attempted``: images the stream handed the consumer in the
    window. ``failed``: images lost or wasted — a sequence gap is a
    message of ``batch`` images that never arrived, a torn or dropped
    message likewise, and every image of an update (one message) whose
    loss was not finite trained nothing."""
    bad_updates = sum(1 for v in losses if not math.isfinite(float(v)))
    failed = batch * (
        int(seq_gaps) + int(torn_messages) + int(dropped_messages)
        + bad_updates
    )
    return int(images_handed), int(failed)
