"""Pipeline engine benchmark: per-frame reference vs chunked vs
streaming executor.

Measures frames/sec of the three scheduling modes on the synthetic
workload (trained proxy, recurrent tracker, gap=1) and emits a
machine-readable ``BENCH_pipeline.json`` so future PRs have a perf
trajectory to regress against.  ``chunk_size`` and ``executor`` fields
distinguish scheduling modes in that trajectory.  Timing uses
``RunResult.seconds`` — process time plus the charged decode ledger,
i.e. the same number the tuner optimizes; wall-clock rates are recorded
separately (prefetch overlaps decode with compute, which process time
by design does not reward).

    PYTHONPATH=src python -m benchmarks.pipeline_bench [--smoke]

Runs are interleaved and the median is reported (this container's
process scheduling is noisy); equivalence of extracted tracks across
all three modes is asserted on every rep.

A second phase (``fps_vs_streams``) scales concurrent streams (1/4/16
threads, one clip run each, per-frame ``chunk_size=1`` — the live
multi-camera regime) with and without a shared ``BatchBroker``,
recording wall fps, consolidated ``detector_dispatches`` and
``batch_fill_mean`` — and asserting both bit-identical tracks and
strictly fewer dispatches at >= 4 streams.  Each stream count also
runs with the device-resident TRACK path on (``device_assign`` through
the fused ``track_step`` kernel, steps coalesced by a shared
``TrackBroker``), recording ``fps_device_track`` /
``track_dispatches`` / ``track_fill_mean`` against the host-tracker
rows.  A chunked-regime phase compares the host tracker against
``DeviceTracker`` (one ``lax.scan`` dispatch per chunk), asserts
bit-identity (also the ``--smoke`` gate), and aggregates the per-stage
``stage_seconds`` utilization block from ``RunResult``.

The proxy threshold comes from the paper's threshold sweep over cached
validation score grids (``proxy.calibrate_threshold``) on a briefly
trained proxy — not from the old self-calibration against the untrained
score distribution.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

DEFAULT_OUT = "BENCH_pipeline.json"


def build_workload(n_clips: int = 4, n_frames: int = 48,
                   train_steps: int = 150, proxy_steps: int = 80):
    from repro.configs.multiscope import MULTISCOPE_PIPELINE
    from repro.core import pipeline as pl
    from repro.core.proxy import (ProxyModel, calibrate_threshold,
                                  cells_from_detections, proxy_loss)
    from repro.core.tracker import init_tracker
    from repro.core.train_models import _fit, train_detector
    from repro.data.video_synth import make_split
    import jax.numpy as jnp

    cfg = MULTISCOPE_PIPELINE.reduced()
    clips = make_split("caldot1", "train", n_clips, n_frames=n_frames)
    det, _ = train_detector("ssd-lite", clips[:2],
                            [cfg.detector.resolutions[-1]],
                            steps=train_steps)
    bank = pl.ModelBank(cfg, {"ssd-lite": det, "ssd-deep": det})
    res = cfg.proxy.resolutions[-1]
    proxy = ProxyModel(cfg.proxy.cell, cfg.proxy.base_channels, res)

    # detector outputs stand in for θ_best labels: train the proxy
    # briefly, then calibrate its threshold with the paper's sweep over
    # cached score grids (replaces the old untrained-quantile hack)
    W, H = cfg.detector.resolutions[-1]
    hc, wc = proxy.grid_shape()
    frames_px, labels, score_frames = [], [], []
    for ci, clip in enumerate(clips[:2]):
        for f in range(0, clip.n_frames, 2):
            frame, _ = pl.render_frame(clip, f, W, H)
            dets = det.detect_batch(frame[None], 0.55)[0]
            lab = cells_from_detections(dets, hc, wc)
            small = pl._downsample(frame, res)
            # hold out every 4th sampled frame of EACH clip (f is
            # always even, so keying on the sample index — not f —
            # keeps both clips contributing calibration frames)
            if (f // 2 + ci) % 4:
                frames_px.append(small)
                labels.append(lab)
            else:                       # held-out calibration frames
                score_frames.append((small, lab))
    rng = np.random.default_rng(0)
    fr = np.stack(frames_px)
    lb = np.stack(labels)

    def batches():
        for _ in range(proxy_steps):
            idx = rng.integers(len(fr), size=8)
            yield (jnp.asarray(fr[idx]), jnp.asarray(lb[idx]))

    params_p, _ = _fit(
        lambda p, f_, l_: proxy_loss(p, f_, l_, cfg.proxy.cell),
        proxy.params, batches(), lr=3e-3)
    proxy.params = params_p
    bank.proxies = {res: proxy}
    bank.sizes_cells = [pl.det_grid(cfg.detector.resolutions[-1]),
                        (3, 2), (5, 3)]
    bank.ref_grid = pl.det_grid(cfg.detector.resolutions[-1])
    bank.tracker_params = init_tracker(cfg.tracker)

    score_grids = [proxy.scores(s, 0.5)[0] for s, _ in score_frames]
    label_grids = [l for _, l in score_frames]
    threshold = calibrate_threshold(score_grids, label_grids,
                                    cfg.proxy.thresholds,
                                    min_recall=0.9)
    params = pl.PipelineParams(
        "ssd-lite", cfg.detector.resolutions[-1], 0.55, gap=1,
        proxy_res=res, proxy_threshold=threshold, tracker="recurrent",
        refine=False)
    return bank, params, clips


def stream_scaling(bank, params, clips, stream_counts=(1, 4, 16),
                   reps: int = 3) -> dict:
    """fps at N concurrent streams (threads, each its own clip run):
    one shared ``BatchBroker`` vs N fully independent runs.

    Streams run in PER-FRAME mode (``chunk_size=1``) — the live multi-
    camera regime the broker targets, where every stream issues one tiny
    detector call per frame and the fixed per-dispatch cost dominates.
    Cross-stream coalescing amortizes exactly that cost; with big chunks
    each stream already makes a couple of large calls per clip and there
    is nothing left to amortize on this host.

    Records the consolidated detector dispatch count and mean bucket
    fill alongside fps — the broker's win is fewer, fuller detector
    calls, and ``detector_dispatches`` must be strictly below the
    independent count from 4 streams up (asserted here, not just
    reported).  Independent/broker fleets alternate within each rep and
    medians are reported (single-core container, very noisy); a warm
    broker fleet runs first so consolidated-bucket conv compiles don't
    land in the measurement."""
    import dataclasses
    import threading

    from repro.core.executor import (BatchBroker, ExecutorOptions,
                                     TrackBroker, run_clip_streamed)

    params = dataclasses.replace(params, chunk_size=1)
    detector = bank.detectors[params.det_arch]

    def fleet(n, broker, track_broker=None, device=False):
        results = [None] * n
        errors = []

        def one(i):
            try:
                opts = ExecutorOptions(prefetch=False,
                                       batch_broker=broker,
                                       device_assign=device,
                                       track_broker=track_broker)
                results[i] = run_clip_streamed(
                    bank, params, clips[i % len(clips)], opts)
            except BaseException as exc:
                errors.append(exc)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errors, errors
        frames = sum(r.frames_processed for r in results)
        return frames / wall, results

    def same_tracks(solo, got, what):
        for a, b in zip(solo, got):
            assert len(a.tracks) == len(b.tracks) and all(
                np.array_equal(x, y)
                for x, y in zip(a.tracks, b.tracks)), \
                f"{what} changed per-stream tracks"

    warm = BatchBroker()
    wtb = TrackBroker()
    _, ref = fleet(max(stream_counts), warm, wtb, device=True)
    warm.close()
    wtb.close()

    out = {}
    for n in stream_counts:
        fps_ind, fps_brk, fps_dev = [], [], []
        disp_ind, disp_brk, fills = [], [], []
        tdisp, tfills = [], []
        for _ in range(reps):
            detector.dispatches = 0
            fps, solo = fleet(n, None)
            fps_ind.append(fps)
            disp_ind.append(detector.dispatches)
            broker = BatchBroker()
            fps, got = fleet(n, broker)
            broker.close()
            fps_brk.append(fps)
            disp_brk.append(broker.dispatches)
            if broker.batch_fill:
                fills.append(float(np.mean(broker.batch_fill)))
            same_tracks(solo, got, "broker")
            # device-resident TRACK on top of the detector broker:
            # per-step assignment through the fused track_step kernel,
            # steps coalesced across streams by a shared TrackBroker
            broker = BatchBroker()
            tb = TrackBroker()
            fps, got = fleet(n, broker, tb, device=True)
            broker.close()
            tb.close()
            fps_dev.append(fps)
            tdisp.append(tb.dispatches)
            if tb.stream_fill:
                tfills.append(float(np.mean(tb.stream_fill)))
            same_tracks(solo, got, "device track path")
        if n >= 4:
            assert max(disp_brk) < min(disp_ind), \
                (n, disp_brk, disp_ind)
        out[str(n)] = {
            "fps_independent": round(float(np.median(fps_ind)), 2),
            "fps_broker": round(float(np.median(fps_brk)), 2),
            "fps_device_track": round(float(np.median(fps_dev)), 2),
            "detector_dispatches_independent": int(np.median(disp_ind)),
            "detector_dispatches": int(np.median(disp_brk)),
            "batch_fill_mean": round(float(np.mean(fills)), 4)
            if fills else 0.0,
            "track_dispatches": int(np.median(tdisp)),
            "track_fill_mean": round(float(np.mean(tfills)), 4)
            if tfills else 0.0,
        }
    return out


def exporter_overhead(bank, params, clips, reps: int = 3,
                      smoke: bool = False, n_streams: int = 4) -> dict:
    """The telemetry serving plane's cost on the hot path: an
    ``n_streams`` broker fleet (per-frame regime) with a live scrape
    loop hammering ``/metrics`` + ``/healthz`` vs the same fleet
    unscraped.

    Runs are PAIRED (off/on back to back, order alternating over an
    EVEN number of reps) and per-stream tracks must be bit-identical
    scraped vs unscraped on every rep — the no-perturbation contract
    on the wire.  The fps row is informational: on a shared host both
    wall and whole-process CPU of an identical fleet jitter by 10-15%
    run to run (broker flush coalescing plus scheduler noise), so a
    sub-percent effect cannot be resolved by differencing two arms.
    ``overhead_pct`` is instead measured directly: the HTTP handler
    threads account their own CPU per request (``ObsServer.stats()``)
    and the overhead is that serving CPU over the scraped arms' total
    process CPU.  Smoke mode asserts it below 1%."""
    import dataclasses
    import threading
    import urllib.request

    from repro.core.executor import (BatchBroker, ExecutorOptions,
                                     run_clip_streamed)
    from repro.obs.serve import ObsServer

    params = dataclasses.replace(params, chunk_size=1)

    def fleet():
        results = [None] * n_streams
        errors = []
        broker = BatchBroker()

        def one(i):
            try:
                opts = ExecutorOptions(prefetch=False,
                                       batch_broker=broker)
                results[i] = run_clip_streamed(
                    bank, params, clips[i % len(clips)], opts)
            except BaseException as exc:
                errors.append(exc)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_streams)]
        c0 = time.process_time()
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        proc = time.process_time() - c0
        broker.close()
        assert not errors, errors
        frames = sum(r.frames_processed for r in results)
        return frames / wall, proc, results

    server = ObsServer(port=0).start()
    stop = threading.Event()
    scrapes = [0]

    def scraper():
        while not stop.is_set():
            for path in ("/metrics", "/healthz"):
                try:
                    urllib.request.urlopen(server.url + path,
                                           timeout=2).read()
                    scrapes[0] += 1
                except Exception:
                    pass
            stop.wait(0.2)

    fleet()                                 # warm both paths' compiles
    reps = max(4, reps + reps % 2)          # even: alternation balances
    fps_on, fps_off, sec_on, sec_off = [], [], [], []
    identical = True
    try:
        for rep in range(reps):
            arms = []
            for scraped in ([False, True] if rep % 2 == 0
                            else [True, False]):
                if scraped:
                    stop.clear()
                    th = threading.Thread(target=scraper, daemon=True)
                    th.start()
                f, s, res = fleet()
                if scraped:
                    stop.set()
                    th.join()
                    fps_on.append(f)
                    sec_on.append(s)
                else:
                    fps_off.append(f)
                    sec_off.append(s)
                arms.append(res)
            for a, b in zip(arms[0], arms[1]):
                identical &= len(a.tracks) == len(b.tracks) and all(
                    np.array_equal(x, y)
                    for x, y in zip(a.tracks, b.tracks))
    finally:
        stop.set()
        server.stop()
    assert identical, \
        "a live /metrics scrape loop perturbed per-stream tracks"
    stats = server.stats()
    serve_cpu = stats["handler_cpu_seconds"]
    overhead_pct = round(100.0 * serve_cpu / sum(sec_on), 3)
    if smoke:
        assert scrapes[0] > 0, "scrape loop never reached the server"
        assert overhead_pct < 1.0, \
            f"exporter overhead {overhead_pct:.2f}% >= 1% " \
            f"({serve_cpu:.4f}s handler CPU over {stats['requests']} " \
            f"requests vs {sum(sec_on):.2f}s scraped-fleet CPU)"
    return {
        "streams": n_streams,
        "fps_scrape_on": round(float(np.median(fps_on)), 2),
        "fps_scrape_off": round(float(np.median(fps_off)), 2),
        "proc_seconds_scrape_on": round(sum(sec_on), 4),
        "proc_seconds_scrape_off": round(sum(sec_off), 4),
        "serve_cpu_seconds": round(serve_cpu, 4),
        "serve_requests": stats["requests"],
        "overhead_pct": overhead_pct,
        "scrapes": scrapes[0],
        "tracks_identical": bool(identical),
    }


def run(out_path: str | None = DEFAULT_OUT, reps: int = 7,
        smoke: bool = False, trace_out: str | None = None) -> dict:
    from repro import obs
    from repro.core import pipeline as pl
    from repro.core.detector import detect_jit_entries
    from repro.core.engine import DEFAULT_CHUNK, run_clip_chunked
    from repro.core.executor import ExecutorOptions, run_clip_streamed

    obs.REGISTRY.reset()
    obs.TRACER.clear()
    if trace_out:
        obs.enable()

    if smoke:
        bank, params, clips = build_workload(n_clips=2, n_frames=24,
                                             train_steps=60,
                                             proxy_steps=40)
        reps = min(reps, 2)
    else:
        bank, params, clips = build_workload()
    chunk = params.chunk_size or DEFAULT_CHUNK
    stream_opts = ExecutorOptions()           # prefetch on, the default

    def sweep():
        """One paired rep: per clip, run the three engines back to back
        so each triple sees the same machine conditions (this
        container's scheduling is noisy; pairing cancels the drift).
        Wall seconds accompany process seconds: prefetch buys wall
        time, not CPU time."""
        s = {"frame": 0.0, "chunked": 0.0, "streaming": 0.0}
        w = {"chunked": 0.0, "streaming": 0.0}
        frames = 0.0
        same = True
        for clip in clips:
            ra = pl.run_clip_frames(bank, params, clip)
            t0 = time.perf_counter()
            rb = run_clip_chunked(bank, params, clip)
            w["chunked"] += time.perf_counter() - t0
            t0 = time.perf_counter()
            rc = run_clip_streamed(bank, params, clip, stream_opts)
            w["streaming"] += time.perf_counter() - t0
            s["frame"] += ra.seconds
            s["chunked"] += rb.seconds
            s["streaming"] += rc.seconds
            frames += ra.frames_processed
            for r in (rb, rc):
                same &= len(ra.tracks) == len(r.tracks) and all(
                    np.array_equal(x, y)
                    for x, y in zip(ra.tracks, r.tracks))
        fps = {k: frames / v for k, v in s.items()}
        wall = {k: frames / v for k, v in w.items()}
        return fps, wall, same

    # warm: jit compiles + render cache for all paths
    sweep()
    entries_warm = detect_jit_entries()

    fps_all = {"frame": [], "chunked": [], "streaming": []}
    wall_all = {"chunked": [], "streaming": []}
    identical = True
    for _ in range(reps):
        fps, wall, same = sweep()
        for k, v in fps.items():
            fps_all[k].append(v)
        for k, v in wall.items():
            wall_all[k].append(v)
        identical &= same

    med = {k: float(np.median(v)) for k, v in fps_all.items()}
    med_wall = {k: float(np.median(v)) for k, v in wall_all.items()}

    # device-resident TRACK in the chunked regime: one chunk-scan
    # dispatch per chunk (DeviceTracker) vs the host per-frame loop —
    # bit-identity asserted every rep (the `--smoke` gate), with the
    # per-stage utilization counters from the device runs aggregated
    # into the `stage_seconds` block
    dev_opts = ExecutorOptions(device_tracker=True)
    fps_dev_all, device_identical = [], True
    dev_blocks = []
    dispatch_sum = {}
    for _ in range(max(2, reps // 2)):
        s_host = s_dev = frames = 0.0
        for clip in clips:
            ra = run_clip_streamed(bank, params, clip, stream_opts)
            rd = run_clip_streamed(bank, params, clip, dev_opts)
            s_host += ra.seconds
            s_dev += rd.seconds
            frames += ra.frames_processed
            device_identical &= len(ra.tracks) == len(rd.tracks) and \
                all(np.array_equal(x, y)
                    for x, y in zip(ra.tracks, rd.tracks))
            if smoke:
                obs.assert_stage_sane(rd.stage_seconds)
            dev_blocks.append(rd.stage_seconds)
            for k, v in rd.dispatches.items():
                dispatch_sum[k] = dispatch_sum.get(k, 0) + v
        fps_dev_all.append(frames / s_dev)
    assert device_identical, \
        "device tracker diverged from the host tracker"
    merged = obs.merge_stage_blocks(dev_blocks)
    if smoke:
        obs.assert_stage_sane(merged)
    stage_seconds = {
        st: {"wall": round(d["wall"], 4),
             "process": round(d["process"], 4)}
        for st, d in merged.items()}

    scaling = stream_scaling(bank, params, clips,
                             stream_counts=(1, 4) if smoke else (1, 4, 16))
    fills = [s["batch_fill_mean"] for s in scaling.values()
             if s["batch_fill_mean"] > 0]

    exporter = exporter_overhead(bank, params, clips, reps=reps,
                                 smoke=smoke)

    result = {
        "benchmark": "pipeline_engine",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "workload": {
            "profile": "caldot1", "clips": len(clips),
            "frames_per_clip": int(clips[0].n_frames),
            "params": params.describe(), "chunk_size": chunk,
            "reps": reps, "smoke": smoke,
        },
        # scheduling-mode fields: the perf trajectory distinguishes the
        # executor mode and chunk size a number was recorded under
        "executor": "streaming",
        "chunk_size": chunk,
        "fps_per_frame": med["frame"],
        "fps_chunked": med["chunked"],
        "fps_streaming": med["streaming"],
        "fps_per_frame_all": [round(f, 2) for f in fps_all["frame"]],
        "fps_chunked_all": [round(f, 2) for f in fps_all["chunked"]],
        "fps_streaming_all": [round(f, 2) for f in fps_all["streaming"]],
        "wall_fps_chunked": med_wall["chunked"],
        "wall_fps_streaming": med_wall["streaming"],
        "fps_streaming_device_tracker":
            float(np.median(fps_dev_all)),
        "device_tracks_identical": bool(device_identical),
        # per-stage utilization (device-tracker runs, summed over
        # clips and reps): wall vs thread-CPU seconds per stage, plus
        # device dispatch counts per stage family
        "stage_seconds": stage_seconds,
        "dispatches": dispatch_sum,
        "speedup": float(np.median(
            [b / a for a, b in zip(fps_all["frame"],
                                   fps_all["chunked"])])),
        "speedup_streaming": float(np.median(
            [b / a for a, b in zip(fps_all["frame"],
                                   fps_all["streaming"])])),
        "tracks_identical": bool(identical),
        # cross-stream broker scaling: wall fps of N concurrent streams
        # sharing one BatchBroker vs N independent runs, plus the
        # consolidated dispatch count and mean bucket occupancy
        "fps_vs_streams": scaling,
        # telemetry serving plane: broker-fleet fps with a live
        # /metrics + /healthz scrape loop vs unscraped, paired reps —
        # smoke asserts <1% process-fps overhead and bit-identical
        # tracks under scrape
        "exporter": exporter,
        "detector_dispatches": {k: v["detector_dispatches"]
                                for k, v in scaling.items()},
        "batch_fill_mean": round(float(np.mean(fills)), 4) if fills
        else 0.0,
        "detector_jit_entries": detect_jit_entries(),
        "jit_entries_grew_after_warmup":
            detect_jit_entries() != entries_warm,
        # registry snapshot: counters/gauges flat, histograms summarized
        # — the same keys bench_diff.py reads for its tolerance gates
        "obs": obs.REGISTRY.snapshot(),
    }
    if trace_out:
        n_spans = obs.export_jsonl(trace_out)
        result["trace"] = {"path": trace_out, "spans": n_spans}
        obs.disable()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    assert identical, \
        "executor diverged from the per-frame path" \
        + (f" (see {out_path})" if out_path else "")
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help=f"output JSON path (default {DEFAULT_OUT})")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload, no file written unless --out "
                         "is explicitly set (CI correctness gate)")
    ap.add_argument("--trace-out", default=None,
                    help="enable tracing and write JSON-lines spans "
                         "here (tracing is off otherwise)")
    ap.add_argument("--serve", type=int, default=None, metavar="PORT",
                    help="expose /metrics, /healthz and /snapshot on "
                         "this port while the bench runs (0 = "
                         "ephemeral; the URL is printed)")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    # default=None keeps an explicit `--out <default path>` detectable
    out = args.out if args.out is not None else \
        (None if args.smoke else DEFAULT_OUT)
    server = None
    if args.serve is not None:
        from repro.obs.serve import ObsServer
        server = ObsServer(port=args.serve).start()
        print(f"obs: serving {server.url}/metrics")
    try:
        r = run(out, reps=args.reps, smoke=args.smoke,
                trace_out=args.trace_out)
    finally:
        if server is not None:
            server.stop()
    print(f"per-frame engine : {r['fps_per_frame']:8.1f} frames/sec")
    print(f"chunked engine   : {r['fps_chunked']:8.1f} frames/sec")
    print(f"streaming engine : {r['fps_streaming']:8.1f} frames/sec"
          f"  (wall {r['wall_fps_streaming']:.1f}/s)")
    print(f"device tracker   : "
          f"{r['fps_streaming_device_tracker']:8.1f} frames/sec"
          f"  (identical: {r['device_tracks_identical']})")
    print(f"speedup          : {r['speedup']:8.2f}x chunked, "
          f"{r['speedup_streaming']:.2f}x streaming")
    print(f"tracks identical : {r['tracks_identical']}")
    for st, d in r["stage_seconds"].items():
        print(f"  stage {st:6s}: {d['wall']:7.2f}s wall "
              f"{d['process']:7.2f}s cpu  "
              f"({r['dispatches'].get(st, '-')} dispatches)")
    for n, s in r["fps_vs_streams"].items():
        print(f"{n:>2} streams       : {s['fps_broker']:8.1f} fps broker"
              f" vs {s['fps_independent']:.1f} independent, "
              f"{s['fps_device_track']:.1f} device-track  "
              f"(dispatches {s['detector_dispatches']} vs "
              f"{s['detector_dispatches_independent']}, "
              f"fill {s['batch_fill_mean']:.2f}; track "
              f"{s['track_dispatches']} @ {s['track_fill_mean']:.2f})")
    e = r["exporter"]
    print(f"exporter overhead: {e['overhead_pct']:.3f}% of fleet CPU "
          f"({e['serve_cpu_seconds']:.4f}s handler CPU over "
          f"{e['serve_requests']} requests vs "
          f"{e['proc_seconds_scrape_on']:.2f}s scraped-fleet CPU, "
          f"{e['scrapes']} scrapes; identical: "
          f"{e['tracks_identical']})")
    print(f"detector jit entries: {r['detector_jit_entries']}"
          f" (stable after warmup: "
          f"{not r['jit_entries_grew_after_warmup']})")
    if out:
        print(f"wrote {out}")
    if args.trace_out:
        print(f"wrote {r['trace']['spans']} spans to {args.trace_out}")


if __name__ == "__main__":
    main()
