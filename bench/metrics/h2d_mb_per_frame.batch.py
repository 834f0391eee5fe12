"""Bytes the program sent to the device per processed frame, MB (1e6
bytes): the sum of ``h2d_bytes`` over every span of the window, each the
``nbytes`` of the padded host array handed to the device (moves
frames_per_s)."""
from bench.lib.child_spans import per_frame


def read(ctx):
    v = per_frame(ctx, (s.args["h2d_bytes"] for s in ctx.spans
                        if s.args and "h2d_bytes" in s.args))
    return None if v is None else v / 1e6
