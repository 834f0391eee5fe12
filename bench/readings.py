#!/usr/bin/env python3
"""Read the compared numbers of one cell on many seeds, program and
control, in one process (one set-up of the models).

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \
        [--seconds 3] [--control | --witness] [--fresh] \
        [--out chiprun_out/readings.jsonl]

For each seed the cell's clips are drawn and rendered, a short window
runs at the cell's own load, and the program's outputs are compared
with the reference; with ``--control`` the control (the reference with
the configuration's lower-precision operands) is read on the same
frames.  The limits in ``bench/limits/<cell>.json`` are set from these
readings: above the largest program reading, below the smallest control
reading.  Each record also gives the verdict of ``compare.verdict``
under those limits for the program and the control.  With
``--witness`` the control is computed at the program's own stated
precision (bfloat16 convolution operands) instead: its distance from
the program and from the full-precision reference tells a rounding at
that precision from a fault.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--witness", action="store_true",
                    help="the control at the program's own precision")
    ap.add_argument("--fresh", action="store_true",
                    help="draw each seed's clips from the seed instead "
                         "of the mix's pool")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        HERE, ".cache", "jax")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench.lib import registry
    from bench.reference.compare import verdict
    from bench.run import enable_cache, limits_for, require_chip
    cell = registry.find_cell(args.workload)
    require_chip(cell.chips)
    from repro.core import pipeline as pl
    enable_cache()
    entry = registry.find_entry(cell.entry)
    prec = cell.config["precision"]
    control = {"conv_operands": prec["control_conv_operands"],
               "host_operands": prec["control_host_operands"]} \
        if args.control else None
    if args.witness:
        control = {"conv_operands": "bfloat16", "host_operands": "bfloat16"}
    limits = limits_for(cell)
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        st = entry.setup(cell, seed, args.seconds, lambda *a: None,
                         fresh=args.fresh)
        t1 = time.perf_counter()
        e2e = entry.window(st)
        t2 = time.perf_counter()
        got = entry.check(st, control=control)
        rec = {"workload": cell.name, "seed": seed, **got,
               "control_operands": control,
               "verdict": {side: verdict(got[side], limits)[0]
                           for side in ("program", "control")
                           if side in got},
               "e2e": e2e, "counters": entry.counters(st),
               "setup_notes": st.setup_notes,
               "seconds": {"setup": t1 - t0, "window": t2 - t1,
                           "check": time.perf_counter() - t2}}
        line = json.dumps(rec, default=float)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        with pl._RENDER_LOCK:
            pl._RENDER_CACHE.clear()       # the next seed's frames only
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
