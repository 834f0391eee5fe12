#!/usr/bin/env python3
"""What the instrumentation costs: one cell's window run four ways in
one process, on the chip.

    python3 bench/tools/trace_cost.py --workload accurate.caldot1 --seconds 30

Prints the end-to-end metric of the same window with (1) nothing on,
(2) the program's span tracer on, (3) the profiler on, (4) both, and
again (1), each after the same set-up.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, "bench", ".cache", "jax")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench.lib import registry, trace
    from bench.run import TRACE_DIR, enable_cache, require_chip
    cell = registry.find_cell(args.workload)
    require_chip(cell.chips)
    enable_cache()
    from repro.obs.trace import TRACER
    entry = registry.find_entry(cell.entry)
    st = entry.setup(cell, args.seed, args.seconds, lambda *a: None)
    for spans, prof in ((False, False), (True, False), (False, True),
                        (True, True), (False, False)):
        if spans:
            TRACER.clear()
            TRACER.enable(capacity=1 << 20)
        box: dict = {}
        try:
            if prof:
                with trace.capture(os.path.join(TRACE_DIR, "cost"), box):
                    e2e = entry.window(st)
            else:
                e2e = entry.window(st)
        finally:
            TRACER.disable()
        red = trace.reduce(box["xplane"], TRACER.snapshot(),
                           box["perf_t0"], cell.chips) if prof else {}
        print(json.dumps({"spans": spans, "profiler": prof, **e2e,
                          **entry.counters(st),
                          "busy_s": red.get("busy_s"),
                          "window_s": red.get("window_s")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
