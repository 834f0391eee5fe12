"""Self time of the executor's ``track.crops`` spans per processed frame, ms:
TRACK's crop cutting on the host (moves frames_per_s)."""
from bench.lib.child_spans import per_frame
from bench.lib.spans import self_ns


def read(ctx):
    v = per_frame(ctx, self_ns(ctx.spans, "track.crops"))
    return None if v is None else v / 1e6
