"""Self time of the executor's ``detect.upload`` spans per processed frame, ms:
DETECT's input preparation on the host and its upload (moves frames_per_s)."""
from bench.lib.child_spans import per_frame
from bench.lib.spans import self_ns


def read(ctx):
    v = per_frame(ctx, self_ns(ctx.spans, "detect.upload"))
    return None if v is None else v / 1e6
