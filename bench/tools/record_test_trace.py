#!/usr/bin/env python3
"""Record the small TPU trace the CPU tests read
(``bench/tests/data/small_tpu.xplane.pb`` and ``small_tpu.json``).

    python3 bench/tools/record_test_trace.py <out_dir>

On one chip: three rounds of the program's detector on 4 full frames at
960x544, a window gather and the detector on its crops, each round
ended by a host sleep of 20 ms, all inside the benchmark's
``bench.window`` capture.  The JSON beside the trace holds what the host
clock saw, so the tests can check the reduction against it.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.lib import trace
    from bench.run import require_chip
    require_chip(1)
    from repro.core.detector import _detect_scores, init_detector
    from repro.kernels.window_gather import window_gather_batch
    params = init_detector("ssd-deep", 0)
    x = jnp.asarray(np.random.default_rng(0).uniform(
        0, 1, (4, 544, 960, 3)).astype(np.float32))
    tbl = jnp.asarray(np.array([[0, 2, 3], [1, 5, 7], [2, 0, 0],
                                [3, 10, 20]], np.int32))

    def work():
        s, b = _detect_scores(params, x, "ssd-deep")
        g = window_gather_batch(x, tbl, win_h=128, win_w=128, cell=16)
        s2, b2 = _detect_scores(params, g, "ssd-deep")
        jax.block_until_ready((s, b, s2, b2))

    work()
    host = []
    box: dict = {}
    with trace.capture(os.path.join(out_dir, "raw"), box):
        for _ in range(3):
            t = time.perf_counter()
            work()
            host.append(time.perf_counter() - t)
            time.sleep(0.02)
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "small_tpu.xplane.pb")
    shutil.copy(box["xplane"], dst)
    shutil.rmtree(os.path.join(out_dir, "raw"))
    pd = jax.profiler.ProfileData.from_file(dst)
    for plane in pd.planes:
        print(plane.name, [(ln.name, len(list(ln.events)),
                            sorted({e.name for e in ln.events})[:8])
                           for ln in plane.lines][:8], flush=True)
    red = trace.reduce(dst, (), box["perf_t0"], 1)
    meta = {"host_work_s": host, "sleep_s": 0.02, "rounds": 3,
            "window_perf_s": (box["perf_t1"] - box["perf_t0"]) / 1e9,
            "device_kind": jax.devices()[0].device_kind,
            "reduced": {k: red[k] for k in ("window_s", "busy_s",
                                            "programs")}}
    with open(os.path.join(out_dir, "small_tpu.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
