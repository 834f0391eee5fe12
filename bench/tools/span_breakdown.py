#!/usr/bin/env python3
"""Where a window's host time goes, by the program's spans, and what
the spans cost: one cell's window run ``--rounds`` times with the span
tracer off, then on (no profiler), after one set-up, on the chip.

    python3 bench/tools/span_breakdown.py --workload accurate.caldot1 --seconds 51 --rounds 3

Prints one JSON object: the end-to-end metric of every window with the
tracer off and on; and, from the last traced window, per span name the
count and the summed duration and self time per processed frame (ms),
and per ``stage.*`` span the share of its summed duration its children
cover, with each child's summed duration.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def breakdown(spans: Sequence, frames: int) -> Dict[str, dict]:
    """-> {"spans": {name: {n, ms_per_frame, self_ms_per_frame}},
    "stages": {stage: {ms_per_frame, covered, children {name:
    ms_per_frame}}}} over the closed spans."""
    from bench.lib.spans import children, covered_ns
    kids = children(spans)
    closed = [s for s in spans if s.dur >= 0]
    per: Dict[str, dict] = {}
    stages: Dict[str, dict] = {}
    for s in closed:
        ks = [k for k in kids.get(s.sid, []) if k.dur >= 0]
        row = per.setdefault(s.name, {"n": 0, "ns": 0, "self_ns": 0})
        row["n"] += 1
        row["ns"] += s.dur
        row["self_ns"] += s.dur - covered_ns(s, ks)
        if s.name.startswith("stage."):
            st = stages.setdefault(s.name, {"ns": 0, "covered_ns": 0,
                                            "children": {}})
            st["ns"] += s.dur
            st["covered_ns"] += covered_ns(s, ks)
            for k in ks:
                st["children"][k.name] = st["children"].get(k.name, 0) \
                    + k.dur
    ms = 1e6 * max(frames, 1)
    return {
        "spans": {n: {"n": r["n"], "ms_per_frame": r["ns"] / ms,
                      "self_ms_per_frame": r["self_ns"] / ms}
                  for n, r in sorted(per.items())},
        "stages": {n: {"ms_per_frame": r["ns"] / ms,
                       "covered": r["covered_ns"] / max(r["ns"], 1),
                       "children": {k: v / ms for k, v in
                                    sorted(r["children"].items())}}
                   for n, r in sorted(stages.items())},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, "bench", ".cache", "jax")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench.lib import registry
    from bench.run import enable_cache, require_chip, spans_window
    cell = registry.find_cell(args.workload)
    require_chip(cell.chips)
    enable_cache()
    entry = registry.find_entry(cell.entry)
    st = entry.setup(cell, args.seed, args.seconds, lambda *a: None)
    off, on = [], []
    for _ in range(args.rounds):
        off.append(entry.window(st))
        spans, counters, e2e = spans_window(entry, st)
        on.append(e2e)
    print(json.dumps({"off": off, "on": on, **counters,
                      **breakdown(spans, counters["frames_processed"])}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
