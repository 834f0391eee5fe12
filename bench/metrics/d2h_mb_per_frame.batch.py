"""Bytes the program pulled from the device per processed frame, MB (1e6
bytes): the sum of ``d2h_bytes`` over every span of the window, each
the ``nbytes`` of the arrays ``detect.wait`` brought to the host (moves
frames_per_s).  A program whose spans carry no ``d2h_bytes`` reads
nothing."""
from bench.lib.child_spans import per_frame


def read(ctx):
    vals = [s.args["d2h_bytes"] for s in ctx.spans
            if s.args and "d2h_bytes" in s.args]
    if not vals:
        return None
    v = per_frame(ctx, vals)
    return None if v is None else v / 1e6
