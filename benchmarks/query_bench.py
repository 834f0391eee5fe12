"""Track-store query benchmark: extract-once-serve-many in numbers.

Measures the quantities the query subsystem promises (``repro.query``):

  * **cold ingest** — fps of materializing the workload's clips into a
    ``TrackStore`` through the streaming executor (paid once per θ);
  * **warm query latency** — median milliseconds per query against the
    warm store, per query shape (limit / count / duration / tracks),
    asserted < 1% of the cold ingest time — PLUS the indexed-vs-scan
    split: the same count query answered from the precomputed
    histograms vs forced through the full row scan
    (``use_index=False``), on a clip set 3× the PR-3 workload;
  * **index pruning** — a selective query whose summaries skip clips
    outright (``skipped_clips``/``scanned_clips`` recorded);
  * **eviction** — a ``StoreBudget`` below the store's footprint is
    installed, LRU eviction brings it under budget (counters
    recorded), and a re-query of evicted clips returns bit-identical
    answers through transparent re-ingest;
  * **throughput** — queries/sec with N concurrent clients hammering
    one ``QueryService``.

Also asserted on every run: re-ingesting a materialized split performs
ZERO detector dispatches, the store-served limit query returns exactly
the frames of the original inline scan, and every indexed answer
equals its full-scan twin.

    PYTHONPATH=src python -m benchmarks.query_bench [--smoke]

Emits ``BENCH_query.json`` (CI uploads it as a workflow artifact).
"""
from __future__ import annotations

import argparse
import json
import tempfile
import threading
import time
from typing import Dict, List

import numpy as np

DEFAULT_OUT = "BENCH_query.json"

REGION = (0.0, 0.5, 1.0, 1.0)           # bottom half (the Table-2 query)
# far corner: provably disjoint from caldot1's highway bands, so the
# index skips every clip without touching a row
SELECTIVE_REGION = (0.0, 0.0, 0.02, 0.02)
MIN_COUNT = 2
WANT = 8


def run(out_path: str | None = DEFAULT_OUT, reps: int = 30,
        clients: int = 4, smoke: bool = False,
        trace_out: str | None = None) -> dict:
    from benchmarks.pipeline_bench import build_workload
    from repro import obs
    from repro.query import QueryService, TrackStore

    obs.REGISTRY.reset()
    obs.TRACER.clear()
    if trace_out:
        obs.enable()

    if smoke:
        bank, params, clips = build_workload(n_clips=2, n_frames=24,
                                             train_steps=60,
                                             proxy_steps=40)
        reps = min(reps, 10)
    else:
        # 3x the PR-3 workload (6 clips x 48 frames): the indexed path
        # must hold its latency as the store grows
        bank, params, clips = build_workload(n_clips=18, n_frames=48)
    det = bank.detectors[params.det_arch]
    fps_clip = clips[0].profile.fps
    spacing = 2 * fps_clip

    root = tempfile.mkdtemp(prefix="query_bench_")
    store = TrackStore(root, bank, params)
    service = QueryService(store)

    try:
        return _measure(det, store, service, clips, reps, clients,
                        smoke, spacing, params, out_path, trace_out)
    finally:
        import shutil
        shutil.rmtree(root, ignore_errors=True)


def _median_ms(service, q, clips, reps, use_index=True) -> float:
    times = []
    for _ in range(reps):
        r = service.query(q, clips, use_index=use_index)
        assert r.stats.ingested_clips == 0
        times.append(r.stats.total_seconds)
    return float(np.median(times) * 1e3)


def _measure(det, store, service, clips, reps, clients, smoke, spacing,
             params, out_path, trace_out=None) -> dict:
    from repro import obs
    from repro.query import Query, StoreBudget, TimeRange
    from repro.query.ref import reference_limit_scan

    # -- cold ingest ----------------------------------------------------------
    report = service.warm(clips)
    assert report.ingested == len(clips)
    cold_s = report.wall_seconds

    # -- re-ingest: zero model work on a warm split ---------------------------
    calls_before = det.dispatches
    report2 = service.warm(clips)
    assert report2.ingested == 0 and det.dispatches == calls_before, \
        "re-ingest of a materialized split touched the detector"
    reingest_calls_warm = det.dispatches - calls_before

    # -- correctness: store-served limit query == inline reference scan ------
    q_limit = Query.limit_frames(region=REGION, min_count=MIN_COUNT,
                                 want=WANT, min_spacing=spacing)
    served = service.query(q_limit, clips)
    reference = reference_limit_scan(
        [store.tracks(c) for c in clips], WANT, MIN_COUNT, REGION,
        spacing)
    identical = served.frames == reference
    assert identical, (served.frames, reference)
    assert served.frames == service.query(
        q_limit, clips, use_index=False).frames

    # -- warm query latency per query shape -----------------------------------
    q_count = Query.count_frames(min_count=MIN_COUNT)   # histogram-served
    queries = {
        "limit": q_limit,
        "count": Query.count_frames(region=REGION, min_count=MIN_COUNT),
        "duration": Query.duration(region=REGION),
        "tracks": Query.count_tracks(
            time_range=TimeRange(0, clips[0].n_frames)),
    }
    latency_ms: Dict[str, float] = {}
    for name, q in queries.items():
        latency_ms[name] = _median_ms(service, q, clips, reps)
    warm_worst_s = max(latency_ms.values()) / 1e3

    # -- indexed vs scan: same count query, histogram vs row scan -------------
    r_idx = service.query(q_count, clips)
    r_scan = service.query(q_count, clips, use_index=False)
    assert r_idx.aggregates == r_scan.aggregates
    # every clip is either skipped by its summary or histogram-served;
    # the row scan is never needed for this predicate
    assert r_idx.indexed_clips == r_idx.scanned_clips
    assert r_idx.indexed_clips + r_idx.skipped_clips == len(clips)
    count_indexed_ms = _median_ms(service, q_count, clips, reps)
    count_scan_ms = _median_ms(service, q_count, clips, reps,
                               use_index=False)

    # -- index pruning: selective region skips whole clips --------------------
    q_sel = Query.count_frames(region=SELECTIVE_REGION)
    r_sel = service.query(q_sel, clips)
    r_sel_scan = service.query(q_sel, clips, use_index=False)
    assert r_sel.aggregates == r_sel_scan.aggregates
    assert r_sel.skipped_clips >= 1, \
        "selective predicate failed to skip any clip via the index"
    selective_ms = _median_ms(service, q_sel, clips, reps)

    # -- eviction: budget below footprint, re-query bit-identically -----------
    q_requery = Query.count_frames(min_count=1)     # needs every clip
    count_before = service.query(q_requery, clips).aggregates
    bytes_before = store.disk_bytes()
    budget_bytes = int(bytes_before * 0.6)
    evicted = store.set_budget(StoreBudget(max_bytes=budget_bytes))
    bytes_after = store.disk_bytes()
    assert evicted >= 1 and bytes_after <= budget_bytes, \
        f"eviction failed: {evicted} evicted, {bytes_after} bytes " \
        f"against a {budget_bytes} budget"
    survivors = [c for c in clips if store.has(c)]
    r_surv = service.query(q_requery, survivors)
    assert r_surv.stats.ingested_clips == 0     # survivors stay warm
    calls0 = det.dispatches
    r_requery = service.query(q_requery, clips)  # transparent re-ingest
    assert r_requery.aggregates == count_before, \
        "re-query after eviction changed the answer"
    reingest_calls = det.dispatches - calls0
    assert r_requery.stats.ingested_clips >= 1
    store.set_budget(None)                      # unbounded again

    # -- concurrent clients ---------------------------------------------------
    per_client = reps
    errs: List[BaseException] = []

    def client(k: int):
        try:
            names = list(queries)
            for i in range(per_client):
                q = queries[names[(k + i) % len(names)]]
                r = service.query(q, clips)
                assert r.stats.ingested_clips == 0
        except BaseException as exc:     # surfaced after join
            errs.append(exc)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(clients)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    conc_wall = time.perf_counter() - t0
    if errs:
        raise errs[0]
    qps = clients * per_client / conc_wall

    warm_over_cold = warm_worst_s / cold_s if cold_s > 0 else 0.0
    latency_ms["count_indexed"] = count_indexed_ms
    latency_ms["count_scan"] = count_scan_ms
    latency_ms["selective_skip"] = selective_ms
    result = {
        "benchmark": "track_store_query",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "workload": {
            "profile": "caldot1", "clips": len(clips),
            "frames_per_clip": int(clips[0].n_frames),
            "params": params.describe(), "reps": reps,
            "clients": clients, "smoke": smoke,
        },
        "store_fingerprint": store.fingerprint,
        "cold_ingest_seconds": cold_s,
        "cold_ingest_fps": report.fps,
        "reingest_detector_calls": reingest_calls_warm,
        "warm_query_ms": latency_ms,
        "warm_over_cold_ratio": warm_over_cold,
        "queries_per_second": qps,
        "limit_query_identical_to_inline_scan": bool(identical),
        "index": {
            "count_indexed_ms": count_indexed_ms,
            "count_scan_ms": count_scan_ms,
            "indexed_clips": int(r_idx.indexed_clips),
            "selective_skipped_clips": int(r_sel.skipped_clips),
            "selective_scanned_clips": int(r_sel.scanned_clips),
            "indexed_equals_scan": True,        # asserted above
        },
        "eviction": {
            "budget_bytes": budget_bytes,
            "bytes_before": bytes_before,
            "bytes_after": bytes_after,
            "evicted_clips": evicted,
            "evicted_bytes": int(store.evicted_bytes),
            "requery_reingest_detector_calls": int(reingest_calls),
            "requery_identical": True,          # asserted above
        },
        # the service's own rollup: per-dataset latency breakdown plus
        # the skip/index/scan clip counters folded over every query run
        "latency_report": service.latency_report(),
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
    assert warm_over_cold < 0.01, \
        f"warm query {warm_worst_s * 1e3:.1f}ms is not <1% of cold " \
        f"ingest {cold_s:.2f}s"
    if not smoke:
        # the acceptance bar: the histogram path must not lose to the
        # row scan even on the 3x clip set (timing assert kept out of
        # smoke/CI where jitter dominates sub-ms medians)
        assert count_indexed_ms <= count_scan_ms * 1.10, \
            f"indexed count {count_indexed_ms:.3f}ms slower than " \
            f"scan {count_scan_ms:.3f}ms"
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help=f"output JSON path (default {DEFAULT_OUT})")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--clients", type=int, default=4)
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
        r = run(out, reps=args.reps, clients=args.clients,
                smoke=args.smoke, trace_out=args.trace_out)
    finally:
        if server is not None:
            server.stop()
    print(f"cold ingest      : {r['cold_ingest_seconds']:8.2f}s "
          f"({r['cold_ingest_fps']:.1f} fps)")
    for name, ms in r["warm_query_ms"].items():
        print(f"warm {name:14s}: {ms:8.3f} ms")
    print(f"warm/cold ratio  : {r['warm_over_cold_ratio']:8.5f} "
          f"(asserted < 0.01)")
    print(f"throughput       : {r['queries_per_second']:8.1f} q/s "
          f"at {r['workload']['clients']} clients")
    idx = r["index"]
    print(f"index            : count {idx['count_indexed_ms']:.3f}ms "
          f"indexed vs {idx['count_scan_ms']:.3f}ms scan; selective "
          f"query skipped {idx['selective_skipped_clips']}/"
          f"{r['workload']['clips']} clips")
    ev = r["eviction"]
    print(f"eviction         : {ev['evicted_clips']} clips "
          f"({ev['evicted_bytes']} B) to fit {ev['budget_bytes']} B; "
          f"re-query identical: {ev['requery_identical']}")
    print(f"re-ingest det calls: {r['reingest_detector_calls']} "
          f"(asserted 0)")
    print(f"identical to inline scan: "
          f"{r['limit_query_identical_to_inline_scan']}")
    for ds, blk in r["latency_report"].get("datasets", {}).items():
        print(f"dataset {ds:10s}: {blk['queries']} queries, "
              f"scan median {blk['scan_seconds_median'] * 1e3:.3f} ms")
    if out:
        print(f"wrote {out}")
    if args.trace_out:
        print(f"wrote {r['trace']['spans']} spans to {args.trace_out}")


if __name__ == "__main__":
    main()
