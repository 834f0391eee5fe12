"""Time the host spent blocked on device results per processed frame,
ms: the summed duration of the ``proxy.wait``, ``detect.wait`` and
``track.wait`` spans, each from dispatch until its outputs are host
arrays (dispatch, transfer and compute together; moves frames_per_s)."""
from bench.lib.child_spans import WAITS, per_frame


def read(ctx):
    v = per_frame(ctx, (s.dur for s in ctx.spans
                        if s.name in WAITS and s.dur >= 0))
    return None if v is None else v / 1e6
