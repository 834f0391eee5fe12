"""Self time of the executor's ``proxy.downsample`` spans per processed frame,
ms: PROXY's host downsample of the chunk to the proxy's resolution (moves
frames_per_s)."""
from bench.lib.child_spans import per_frame
from bench.lib.spans import self_ns


def read(ctx):
    v = per_frame(ctx, self_ns(ctx.spans, "proxy.downsample"))
    return None if v is None else v / 1e6
