"""Self time of the executor's ``stage.proxy`` spans per processed frame, ms
(layer: the proxy stage; moves frames_per_s)."""
from bench.lib.spans import self_ns


def read(ctx):
    frames = ctx.counters.get("frames_processed", 0)
    ns = self_ns(ctx.spans, "stage.proxy")
    if not frames or not ns:
        return None
    return sum(ns) / 1e6 / frames
