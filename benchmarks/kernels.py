"""Kernel micro-benchmarks: µs/call of each op's CPU execution path plus
the analytic TPU-target roofline estimate per kernel.

On this CPU container the Pallas kernels execute in interpret mode (not
representative of TPU speed), so the measured numbers benchmark the jnp
dispatch path that the dry-run lowers; the analytic columns give the
TPU v5e expectation (bytes / 819 GB/s vs FLOPs / 197 TFLOP/s).

    PYTHONPATH=src python -m benchmarks.kernels [--smoke]

``--smoke`` is the CI correctness gate: it auto-discovers every kernel
package under ``src/repro/kernels/`` (any directory with a
``kernel.py``) and runs its ``smoke.py:smoke()`` — interpret-mode
Pallas vs the jnp reference, the same contract the kernel tests
enforce, runnable without pytest.  The ``kernel-contract`` pass of
``python -m repro.analysis`` verifies every package ships that entry.
"""
from __future__ import annotations

import argparse
import importlib
import time
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PEAK = 197e12
BW = 819e9


def _time(fn, *args, iters: int = 5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6    # µs


def run() -> List[Dict]:
    rows = []
    key = jax.random.PRNGKey(0)

    from repro.kernels.flash_attention import flash_attention
    B, S, Hq, Hkv, D = 1, 1024, 8, 2, 64
    q = jax.random.normal(key, (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)
    us = _time(lambda: flash_attention(q, k, v))
    flops = 4 * B * S * S * Hq * D
    rows.append({"name": f"flash_attention B{B} S{S} H{Hq}/{Hkv} D{D}",
                 "us_per_call": us,
                 "tpu_est_us": flops / PEAK * 1e6})

    from repro.kernels.decode_attention import decode_attention
    B, S, Hq, Hkv, D = 8, 8192, 8, 2, 64
    q = jax.random.normal(key, (B, Hq, D), jnp.float32)
    k = jax.random.normal(key, (B, S, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, Hkv, D), jnp.bfloat16)
    kvlen = jnp.full((B,), S, jnp.int32)
    us = _time(lambda: decode_attention(q, k, v, kvlen))
    bytes_ = B * S * Hkv * D * 2 * 2
    rows.append({"name": f"decode_attention B{B} S{S}",
                 "us_per_call": us, "tpu_est_us": bytes_ / BW * 1e6})

    from repro.kernels.ssd_scan import ssd_scan
    b, S, H, P, N = 1, 2048, 8, 64, 64
    x = jax.random.normal(key, (b, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key, (b, S, H)))
    A = -jnp.exp(jax.random.normal(key, (H,)) * 0.3)
    Bm = jax.random.normal(key, (b, S, N)) * 0.5
    C = jax.random.normal(key, (b, S, N)) * 0.5
    Dv = jax.random.normal(key, (H,)) * 0.1
    us = _time(lambda: ssd_scan(x, dt, A, Bm, C, Dv))
    Q = 128
    flops = b * H * (S // Q) * (2 * Q * Q * N + 2 * Q * Q * P
                                + 2 * Q * N * P * 2)
    rows.append({"name": f"ssd_scan S{S} H{H} P{P} N{N}",
                 "us_per_call": us, "tpu_est_us": flops / PEAK * 1e6})

    from repro.kernels.proxy_score import proxy_score
    feat = jax.random.normal(key, (1, 24, 32, 64), jnp.float32)
    w = jax.random.normal(key, (64,))
    us = _time(lambda: proxy_score(feat, w, 0.0, 0.5))
    rows.append({"name": "proxy_score 24x32x64",
                 "us_per_call": us,
                 "tpu_est_us": feat.size * 4 / BW * 1e6})

    from repro.kernels.window_gather import window_gather
    frame = jax.random.normal(key, (512, 768, 3), jnp.float32)
    oc = jnp.array([[0, 0], [2, 4], [4, 8], [6, 2]], jnp.int32)
    us = _time(lambda: window_gather(frame, oc, win_h=128, win_w=128))
    rows.append({"name": "window_gather 4x128x128",
                 "us_per_call": us,
                 "tpu_est_us": 4 * 128 * 128 * 3 * 4 * 2 / BW * 1e6})

    from repro.kernels.proxy_plan import proxy_plan
    B, hp, wp, C, hc, wc = 16, 24, 32, 64, 5, 8
    feat = jax.random.normal(key, (B, hp, wp, C), jnp.float32)
    w = jax.random.normal(key, (C,))
    us = _time(lambda: proxy_plan(feat, w, 0.0, 0.5, grid_hw=(hc, wc)))
    rows.append({"name": f"proxy_plan B{B} {hp}x{wp}x{C}->{hc}x{wc}",
                 "us_per_call": us,
                 "tpu_est_us": feat.size * 4 / BW * 1e6})

    from repro.kernels.assign import assign_batch
    K, N = 16, 32
    costs = jax.random.uniform(key, (K, N, N), jnp.float32)
    us = _time(lambda: assign_batch(costs))
    # JV augmenting paths: ~N scans of the NxN slack matrix per row
    rows.append({"name": f"assign_batch K{K} N{N}",
                 "us_per_call": us,
                 "tpu_est_us": K * N * N * N * 4 / BW * 1e6})

    from repro.kernels.track_step import (pack_params, track_step)
    from repro.kernels.track_step.ops import LOG1P_TABLE_2D
    from repro.kernels.track_step.smoke import track_operands
    K, Q, H, e, M = 8, 32, 32, 16, 32
    arrs, thr, np_params = track_operands(
        np.random.default_rng(0), K, Q, H, e, M)
    packed = pack_params(np_params)
    jarrs = [jnp.asarray(a) for a in arrs]
    jthr = jnp.asarray(thr)
    us = _time(lambda: track_step(*jarrs, jthr, packed, LOG1P_TABLE_2D))
    # matmuls (GRU + match head) on the MXU, JV slack scans on the VPU
    flops = K * (6 * Q * (e + H) * H
                 + 2 * Q * Q * ((H + e + 6) * M + M))
    rows.append({"name": f"track_step K{K} Q{Q} H{H} e{e}",
                 "us_per_call": us,
                 "tpu_est_us": (flops / PEAK
                                + K * Q * Q * Q * 4 / BW) * 1e6})
    return rows


def discover_kernel_packages() -> List[str]:
    """Kernel package names: directories under ``src/repro/kernels/``
    that contain a ``kernel.py``."""
    import repro.kernels
    root = Path(repro.kernels.__file__).parent
    return sorted(p.name for p in root.iterdir()
                  if p.is_dir() and (p / "kernel.py").is_file())


def smoke() -> None:
    """CI gate: run every kernel package's smoke.py — interpret-mode
    Pallas output vs the jnp reference."""
    names = discover_kernel_packages()
    assert names, "no kernel packages discovered"
    for name in names:
        mod = importlib.import_module(f"repro.kernels.{name}.smoke")
        mod.smoke()
        print(f"kernels smoke OK: {name}")
    print(f"kernels smoke OK: {len(names)} packages")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="correctness gate only (no timing sweep)")
    ap.add_argument("--serve", type=int, default=None, metavar="PORT",
                    help="expose /metrics, /healthz and /snapshot on "
                         "this port while the bench runs (0 = "
                         "ephemeral; the URL is printed)")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    server = None
    if args.serve is not None:
        from repro.obs.serve import ObsServer
        server = ObsServer(port=args.serve).start()
        print(f"obs: serving {server.url}/metrics")
    try:
        if args.smoke:
            smoke()
            return
        print("name,us_per_call,tpu_est_us")
        for r in run():
            print(f"{r['name']},{r['us_per_call']:.1f},"
                  f"{r['tpu_est_us']:.2f}")
    finally:
        if server is not None:
            server.stop()


if __name__ == "__main__":
    main()
