"""Self time of the executor's ``stage.decode`` spans per processed frame, ms
(layer: the decode stage; moves frames_per_s)."""
from bench.lib.spans import self_ns


def read(ctx):
    frames = ctx.counters.get("frames_processed", 0)
    ns = self_ns(ctx.spans, "stage.decode")
    if not frames or not ns:
        return None
    return sum(ns) / 1e6 / frames
