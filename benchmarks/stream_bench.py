"""Live-ingestion benchmark: segment appends + standing queries in
numbers.

Measures the quantities the stream subsystem promises (``repro.stream``):

  * **append latency** — wall time per appended segment, split into
    executor (decode/proxy/detect/track over the segment), index merge
    + store landing, and standing-query delta evaluation;
  * **watermark lag** — how long after a segment's last frame arrives
    until queries can see it (store landing + standing notification);
  * **standing-query delta latency** — per registered query, the
    incremental re-evaluation cost per watermark advance, vs
    **re-running the ad-hoc query from scratch** (the full row scan,
    ``use_index=False``) over the same open clips;
  * **fleet watermark lag, broker off/on/track** — K feeds appending
    concurrently (one ingestor + thread each, per-frame segments) with
    a shared ``executor.BatchBroker`` vs independent executors, plus a
    third mode adding the device-resident TRACK path (fused
    ``track_step`` assignment, steps coalesced by a shared
    ``TrackBroker``): lag, append wall, fleet fps, consolidated
    detector/track dispatches and per-stage ``stage_seconds``, with
    per-feed stored rows asserted bit-identical across all modes;
  * **exactness counters** — the unrestricted standing query must scan
    each visible row EXACTLY once across the whole stream
    (``rows_scanned == total rows``), and its accumulated state must
    equal the ad-hoc answer and the naive ``ref.reference_query``
    oracle at the final watermark.

The non-smoke run keeps 24 clips open simultaneously and asserts the
standing delta evaluation serves >= 10x faster than the cold ad-hoc
re-run (the acceptance bar); ``--smoke`` is the CI correctness gate —
tiny workload, every equality asserted (including sealed-vs-batch
bit-identity), timing asserts skipped where jitter dominates.

    PYTHONPATH=src python -m benchmarks.stream_bench [--smoke]

Emits ``BENCH_stream.json`` (CI uploads it as a workflow artifact).
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from typing import Dict, List

import numpy as np

DEFAULT_OUT = "BENCH_stream.json"

REGION_TOP = (0.0, 0.0, 1.0, 0.5)


def run(out_path: str | None = DEFAULT_OUT, smoke: bool = False,
        trace_out: str | None = None) -> dict:
    from benchmarks.pipeline_bench import build_workload
    from repro import obs
    from repro.query import Query, QueryService, TrackStore
    from repro.query.ref import reference_query
    from repro.stream import SegmentIngestor, StandingQuery

    obs.REGISTRY.reset()
    obs.TRACER.clear()
    if trace_out:
        obs.enable()

    if smoke:
        bank, params, clips = build_workload(n_clips=3, n_frames=24,
                                             train_steps=60,
                                             proxy_steps=40)
        segment = 8
    else:
        # 24 always-on cameras, 48-frame days, 12-frame segments — the
        # delta-vs-rescan gap must hold with 4+ clips open at once
        # (delta cost is per appended clip; the rescan pays O(clips))
        bank, params, clips = build_workload(n_clips=24, n_frames=48)
        segment = 12
    n_frames = clips[0].n_frames
    root = tempfile.mkdtemp(prefix="stream_bench_")
    try:
        return _measure(bank, params, clips, segment, n_frames, root,
                        smoke, out_path, trace_out,
                        Query, QueryService, TrackStore,
                        reference_query, SegmentIngestor, StandingQuery)
    finally:
        import shutil
        shutil.rmtree(root, ignore_errors=True)


def _fleet_lag(bank, params, clips, segment, root, smoke,
               TrackStore, SegmentIngestor) -> dict:
    """Watermark lag with K camera feeds appending CONCURRENTLY (one
    ingestor + thread per feed, per-frame ``chunk_size=1``), with a
    shared ``BatchBroker`` vs fully independent executors.

    The broker consolidates every feed's per-segment detector windows
    into shared dispatches; per-feed stored rows must stay bit-identical
    (asserted), only the batching and the lag/throughput change.  Lag
    here is the bench's usual store-landing + standing-notify slice of
    each append; append wall and fleet fps are recorded alongside so
    the linger the broker spends waiting for peers is visible too.
    The "track" mode keeps the detector broker and moves TRACK onto
    the device as well (``device_assign`` + shared ``TrackBroker``) —
    the fleet-phase row for the fused track_step path."""
    import dataclasses
    import os
    import threading

    from repro import obs
    from repro.core.executor import (BatchBroker, ExecutorOptions,
                                     TrackBroker)

    p1 = dataclasses.replace(params, chunk_size=1)
    feeds = clips[:3] if smoke else clips[:8]
    detector = bank.detectors[params.det_arch]
    out = {"feeds": len(feeds), "segment_frames": segment}
    rows_by_mode = {}
    # "track" = detector broker PLUS the device-resident TRACK path:
    # per-step assignment through the fused track_step kernel, steps
    # coalesced across feeds by a shared TrackBroker.  "warm" is an
    # unrecorded track-mode fleet run first so the fused kernel's jit
    # compiles (one per padded batch/slot shape) don't land in the
    # measured appends.
    for mode in ("warm", "off", "on", "track"):
        broker = BatchBroker() if mode != "off" else None
        track_broker = TrackBroker() if mode in ("warm", "track") \
            else None
        detector.dispatches = 0
        stores, ingestors = [], []
        for i, c in enumerate(feeds):
            s = TrackStore(os.path.join(root, f"fleet_{mode}_{i}"),
                           bank, p1)
            ing = SegmentIngestor(
                s, options=ExecutorOptions(
                    prefetch=False, batch_broker=broker,
                    device_assign=mode in ("warm", "track"),
                    track_broker=track_broker))
            ing.open(c)
            stores.append(s)
            ingestors.append(ing)
        reports = [[] for _ in feeds]
        errors: List[BaseException] = []

        def run_feed(i):
            try:
                c = feeds[i]
                n_seg = (c.n_frames + segment - 1) // segment
                for _ in range(n_seg):
                    reports[i].append(ingestors[i].append(c, segment))
            except BaseException as exc:
                errors.append(exc)

        threads = [threading.Thread(target=run_feed, args=(i,))
                   for i in range(len(feeds))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if broker is not None:
            broker.close()
        if track_broker is not None:
            track_broker.close()
        assert not errors, errors
        if mode == "warm":
            continue
        flat = [r for rs in reports for r in rs]
        assert all(rs[-1].sealed for rs in reports)
        lag = [r.store_seconds + r.standing_seconds for r in flat]
        out[f"watermark_lag_ms_broker_{mode}"] = {
            "median": float(np.median(lag) * 1e3),
            "p95": float(np.percentile(lag, 95) * 1e3),
        }
        out[f"append_wall_ms_broker_{mode}"] = float(
            np.median([r.wall_seconds for r in flat]) * 1e3)
        out[f"fleet_fps_broker_{mode}"] = round(
            sum(r.frames_processed for r in flat) / wall, 2)
        out[f"detector_dispatches_broker_{mode}"] = int(
            broker.dispatches if broker is not None
            else detector.dispatches)
        # per-stage utilization summed over every append in the fleet
        stage = obs.merge_stage_blocks(r.stage_seconds for r in flat)
        if smoke:
            obs.assert_stage_sane(stage)
        out[f"stage_seconds_broker_{mode}"] = {
            st: {k: round(v, 4) for k, v in d.items()}
            for st, d in stage.items()}
        if track_broker is not None:
            out["track_dispatches"] = track_broker.dispatches
            out["track_steps_in"] = track_broker.steps_in
            out["track_fill_mean"] = round(
                float(np.mean(track_broker.stream_fill)), 4) \
                if track_broker.stream_fill else 0.0
        rows_by_mode[mode] = [stores[i].get(c).rows
                              for i, c in enumerate(feeds)]
    for mode in ("on", "track"):
        for a, b in zip(rows_by_mode["off"], rows_by_mode[mode]):
            np.testing.assert_array_equal(a, b)
    out["tracks_bit_identical"] = True
    assert out["detector_dispatches_broker_on"] \
        < out["detector_dispatches_broker_off"]
    return out


def _measure(bank, params, clips, segment, n_frames, root, smoke,
             out_path, trace_out, Query, QueryService, TrackStore,
             reference_query, SegmentIngestor, StandingQuery) -> dict:
    import os

    from repro import obs

    store = TrackStore(os.path.join(root, "live"), bank, params)
    service = QueryService(store)
    ingestor = SegmentIngestor(store, service=service)
    q_count = Query.count_frames(min_count=1)
    q_region = Query.count_frames(region=REGION_TOP, min_count=1)
    n_sqs = 2

    for c in clips:
        ingestor.open(c)

    append_wall: List[float] = []
    append_exec: List[float] = []
    append_store: List[float] = []
    append_standing: List[float] = []
    adhoc_total_s: List[float] = []
    adhoc_scan_s: List[float] = []
    reports = []
    n_segments = (n_frames + segment - 1) // segment
    # Phase A (first half of the stream): both standing queries
    # registered — their per-watermark delta evaluation is timed in
    # the post-append slot.  Phase B (second half): the timed query is
    # UNREGISTERED and keeping its answer fresh reverts to the
    # baseline world — re-running the ad-hoc query after every
    # watermark advance, timed in the same post-append slot.  Delta
    # cost is independent of accumulated history (it folds one
    # segment's new rows), so giving the rescan the LARGER second-half
    # store is the conservative comparison; the region query stays
    # registered to the end for the full-stream exactness asserts.
    sq_count = service.register_standing(StandingQuery(q_count, clips))
    sq_region = service.register_standing(
        StandingQuery(q_region, clips))
    timed_standing = True
    for si in range(n_segments):
        if si == (n_segments + 1) // 2 and timed_standing:
            timed_standing = False
            mid_rows = sum(len(store.get(c).rows) for c in clips)
            assert sq_count.rows_scanned == mid_rows, \
                f"standing query scanned {sq_count.rows_scanned} " \
                f"rows, stream delivered {mid_rows}: a row was " \
                f"rescanned"
            mid_scanned = sq_count.rows_scanned
            service.unregister_standing(sq_count)
        for c in clips:
            rep = ingestor.append(c, segment)
            reports.append(rep)
            append_wall.append(rep.wall_seconds)
            append_exec.append(rep.wall_seconds - rep.store_seconds
                               - rep.standing_seconds)
            append_store.append(rep.store_seconds)
            if timed_standing:
                append_standing.append(rep.standing_seconds)
            else:
                r = service.query(q_count, clips, use_index=False)
                adhoc_total_s.append(r.stats.total_seconds)
                adhoc_scan_s.append(r.stats.scan_seconds)
        # per-watermark exactness: accumulated state == ad-hoc
        live_sqs = ((sq_count, q_count), (sq_region, q_region)) \
            if timed_standing else ((sq_region, q_region),)
        for sq, q in live_sqs:
            acc = sq.result()
            adhoc = service.query(q, clips)
            assert acc.aggregates == adhoc.aggregates, \
                (si, acc.aggregates, adhoc.aggregates)
    assert all(r.sealed for r in reports[-len(clips):])

    # per-stage executor seconds summed over every append of the
    # single-stream phase (the fleet phase reports its own blocks)
    stage_totals = obs.merge_stage_blocks(
        r.stage_seconds for r in reports)
    if smoke:
        obs.assert_stage_sane(stage_totals)

    # -- exactness counters ---------------------------------------------------
    total_rows = sum(len(store.get(c).rows) for c in clips)
    # every delivered row is exactly one of scanned / summary-skipped
    # (a summary-disjoint delta is dropped whole, rows uncounted)
    assert sq_region.rows_scanned + sq_region.rows_skipped \
        == total_rows, \
        f"standing query scanned {sq_region.rows_scanned} + skipped " \
        f"{sq_region.rows_skipped} rows, stream delivered " \
        f"{total_rows}: a row was rescanned or lost"
    ref = reference_query(
        [store.tracks(c) for c in clips],
        [c.profile.fps for c in clips],
        region=REGION_TOP,
        min_len=2, min_count=1, aggregate="count")
    assert sq_region.result().aggregates == ref["aggregates"]

    if smoke:
        # sealed stream == one-shot batch ingest, bit for bit
        batch = TrackStore(os.path.join(root, "batch"), bank, params)
        batch.ingest(clips)
        for c in clips:
            a, b = batch.get(c), store.get(c)
            np.testing.assert_array_equal(a.rows, b.rows)
            np.testing.assert_array_equal(a.hist, b.hist)
            assert a.summary == b.summary and a.counters == b.counters

    fleet = _fleet_lag(bank, params, clips, segment, root, smoke,
                       TrackStore, SegmentIngestor)

    delta_ms = float(np.median(append_standing) / n_sqs * 1e3)
    adhoc_ms = float(np.median(adhoc_total_s) * 1e3)
    adhoc_scan_ms = float(np.median(adhoc_scan_s) * 1e3)
    speedup = adhoc_ms / delta_ms if delta_ms > 0 else float("inf")
    lag = [r.store_seconds + r.standing_seconds for r in reports]
    frames_appended = sum(r.frames_processed for r in reports)
    result = {
        "benchmark": "stream_ingest",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "workload": {
            "profile": "caldot1", "clips": len(clips),
            "frames_per_clip": n_frames, "segment_frames": segment,
            "segments": len(reports),
            "params": params.describe(), "smoke": smoke,
        },
        "append_ms": {
            "median": float(np.median(append_wall) * 1e3),
            "p95": float(np.percentile(append_wall, 95) * 1e3),
            "executor_median": float(np.median(append_exec) * 1e3),
            "store_median": float(np.median(append_store) * 1e3),
            "standing_median": float(np.median(append_standing) * 1e3),
        },
        "append_fps": frames_appended / max(sum(append_wall), 1e-9),
        "watermark_lag_ms": {
            "median": float(np.median(lag) * 1e3),
            "p95": float(np.percentile(lag, 95) * 1e3),
        },
        "standing_delta_ms": delta_ms,
        "adhoc_query_ms": adhoc_ms,
        "adhoc_scan_ms": adhoc_scan_ms,
        "delta_speedup_over_adhoc": speedup,
        "rows_total": int(total_rows),
        "standing_rows_scanned": int(sq_region.rows_scanned),
        "standing_rows_skipped": int(sq_region.rows_skipped),
        "midpoint_rows_scanned_once": int(mid_scanned),
        "rows_scanned_exactly_once": True,      # asserted above
        "standing_matches_adhoc_and_reference": True,
        "open_clips_during_adhoc_measure": len(clips),
        "stage_seconds": {
            st: {k: round(v, 4) for k, v in d.items()}
            for st, d in stage_totals.items()},
        "fleet": fleet,
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
    if not smoke:
        # the acceptance bar (timing asserts stay out of smoke/CI where
        # sub-ms medians are jitter-dominated)
        assert speedup >= 10.0, \
            f"standing delta {delta_ms:.4f}ms only {speedup:.1f}x " \
            f"faster than ad-hoc scan {adhoc_ms:.4f}ms (need 10x)"
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help=f"output JSON path (default {DEFAULT_OUT})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload (CI correctness gate)")
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
    out = args.out if args.out is not None else DEFAULT_OUT
    server = None
    if args.serve is not None:
        from repro.obs.serve import ObsServer
        server = ObsServer(port=args.serve).start()
        print(f"obs: serving {server.url}/metrics")
    try:
        r = run(out, smoke=args.smoke, trace_out=args.trace_out)
    finally:
        if server is not None:
            server.stop()
    a = r["append_ms"]
    print(f"append latency   : {a['median']:8.2f} ms median "
          f"(p95 {a['p95']:.2f}; executor {a['executor_median']:.2f} "
          f"+ store {a['store_median']:.2f} "
          f"+ standing {a['standing_median']:.2f})")
    print(f"append throughput: {r['append_fps']:8.1f} frames/s wall")
    w = r["watermark_lag_ms"]
    print(f"watermark lag    : {w['median']:8.2f} ms median "
          f"(p95 {w['p95']:.2f})")
    print(f"standing delta   : {r['standing_delta_ms']:8.4f} ms vs "
          f"ad-hoc re-run {r['adhoc_query_ms']:.4f} ms "
          f"(scan {r['adhoc_scan_ms']:.4f}) -> "
          f"{r['delta_speedup_over_adhoc']:.1f}x "
          f"at {r['open_clips_during_adhoc_measure']} open clips")
    print(f"rows scanned once: {r['standing_rows_scanned']} scanned "
          f"+ {r['standing_rows_skipped']} summary-skipped == "
          f"{r['rows_total']} (asserted)")
    fl = r["fleet"]
    for mode in ("off", "on", "track"):
        w = fl[f"watermark_lag_ms_broker_{mode}"]
        print(f"fleet broker {mode:>5}: "
              f"{fl[f'fleet_fps_broker_{mode}']:8.1f} fps, lag "
              f"{w['median']:.2f} ms median (p95 {w['p95']:.2f}), "
              f"{fl[f'detector_dispatches_broker_{mode}']} dispatches "
              f"at {fl['feeds']} feeds")
    print(f"fleet track path : {fl['track_dispatches']} coalesced "
          f"track dispatches for {fl['track_steps_in']} steps "
          f"(fill {fl['track_fill_mean']:.2f})")
    if out:
        print(f"wrote {out}")
    if args.trace_out:
        print(f"wrote {r['trace']['spans']} spans to {args.trace_out}")


if __name__ == "__main__":
    main()
