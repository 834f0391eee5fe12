"""Self time of the executor's ``detect.decode`` spans per processed frame, ms:
DETECT's host decode and NMS of every window, and the per-frame merge (moves
frames_per_s)."""
from bench.lib.child_spans import per_frame
from bench.lib.spans import self_ns


def read(ctx):
    v = per_frame(ctx, self_ns(ctx.spans, "detect.decode"))
    return None if v is None else v / 1e6
