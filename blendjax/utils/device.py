"""Small device-array helpers shared across the streaming/train layers."""

from __future__ import annotations


def transfer_done(arr) -> bool:
    """Non-blocking readiness poll for an in-flight device array. ONE
    definition for the feeder's throttle window and the TrainDriver's
    dispatch ring, so their retirement semantics cannot diverge."""
    return bool(arr.is_ready())
