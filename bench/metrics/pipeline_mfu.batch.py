"""The whole window's share of the chip's peak FLOP/s, %: the proxy's
and the detector's operations for the work done (``lib/flops``) over
the traced window times the peak.  Sub-frame windows are counted at the
smallest size, so the share errs low.  The counts are the profiled
window's own (moves frames_per_s)."""
from bench.lib.flops import detector_work, proxy_work


def read(ctx):
    if not ctx.device or ctx.device["window_s"] <= 0:
        return None
    ops = detector_work(ctx.config, ctx.theta, ctx.sizes_cells,
                        ctx.device_counters, ctx.cell.root)[0]
    ops += proxy_work(ctx.config, ctx.theta, ctx.device_counters)[0]
    chips = ctx.cell.chips
    return 100.0 * ops / (ctx.device["window_s"] * chips
                          * ctx.peaks["flops_per_s"])
