"""The detector's share of its roofline, %: the least time its work
could take on this chip (the larger of operations over peak FLOP/s and
bytes over peak bandwidth, ``lib/flops.detector_work``) over the device
time of its programs (``jit__detect_scores``) in the trace.  Sub-frame
windows are counted at the smallest size of the set, so the share errs
low.  The counts are the profiled window's own (moves frames_per_s)."""
from bench.lib.flops import detector_work

PROGRAM = "jit__detect_scores"


def read(ctx):
    t = ctx.device["programs"].get(PROGRAM, 0.0) if ctx.device else 0.0
    if t <= 0:
        return None
    ops, byt = detector_work(ctx.config, ctx.theta, ctx.sizes_cells,
                             ctx.device_counters, ctx.cell.root)
    least = max(ops / ctx.peaks["flops_per_s"],
                byt / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
