"""Self time of the executor's ``track.assoc`` spans per processed frame, ms:
TRACK's association (host features, match MLP, JV, GRU) (moves
frames_per_s)."""
from bench.lib.child_spans import per_frame
from bench.lib.spans import self_ns


def read(ctx):
    v = per_frame(ctx, self_ns(ctx.spans, "track.assoc"))
    return None if v is None else v / 1e6
