"""Per-frame readings of the spans the executor opens inside its PROXY,
DETECT and TRACK stages (``proxy.*``, ``detect.*``, ``track.*``).

A program that opens none of them in a window that ran (one built before
they existed) reads ``None``, and its line leaves the metric out; a
program that opens some reads ``0.0`` for a kind that did not occur in
the window (a chunk with no detections cuts no crops).
"""
from __future__ import annotations

from typing import Iterable, Optional

CHILDREN = ("proxy.downsample", "proxy.wait", "detect.upload",
            "detect.wait", "detect.decode", "track.crops", "track.wait",
            "track.assoc")
WAITS = ("proxy.wait", "detect.wait", "track.wait")


def per_frame(ctx, values: Iterable[float]) -> Optional[float]:
    """``sum(values)`` per processed frame of the unprofiled window, or
    ``None`` where the window ran no frame or the program has no child
    spans."""
    frames = ctx.counters.get("frames_processed", 0)
    if not frames or not any(s.name in CHILDREN for s in ctx.spans):
        return None
    return float(sum(values)) / frames
