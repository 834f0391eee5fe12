#!/usr/bin/env python3
"""Drive the MultiScope pipeline once on a TPU at the paper's widths.

    python chip_smoke.py              # one chip: four phases
    python chip_smoke.py --chips 4    # four chips: the multi-chip phase only

The configuration is ``MULTISCOPE_PIPELINE`` as published (960x544
frames and detector, 416x256 proxy with 32 px cells and 64 feature
channels, tracker embed 32 / GRU 64 / match 64 / 64 tracks, k=3 window
sizes, up to 8 windows a frame).  Weights come from seed 0 and a short
training run here (``train_models.train_detector`` and the proxy fit of
``benchmarks/pipeline_bench.py``); scenes from ``video_synth``.

One-chip phases, each printing one JSON line of findings:

  1. extraction   — ``pipeline.run_clip`` (streaming executor, fused
                    proxy plan, window gathers, host recurrent tracker)
                    against the per-frame ``run_clip_frames``;
  2. device_track — the same clips with ``device_tracker=True`` against
                    phase 1;
  3. fleet        — 4 concurrent streams sharing one ``BatchBroker`` and
                    one ``TrackBroker`` (``device_assign=True``) against
                    the solo runs of phase 1;
  4. store        — ``TrackStore`` ingest, live ``SegmentIngestor``
                    appends and ``QueryService`` queries, whose answers
                    must equal ``query.ref.reference_query`` exactly.

Phases 1-3 pass when each clip's MOTA is within ``MOTA_TOL`` of the
comparison path's and there is at least one detection and one track of
at least ``MIN_TRACK`` frames; bit-identity and the largest box
difference are printed as findings.  Phase 1 also requires at least one
sub-frame window to have been gathered.  Set-up seconds are cold: they
include compilation.  Any failed check raises, so the script exits
non-zero without its final line.  The last line of standard output is
``{"ok": true, "device": {...}}``.  Without a TPU it exits with 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

MOTA_TOL = 0.01          # |MOTA(path) - MOTA(comparison)| per clip
MIN_TRACK = 8            # frames in the longest track, at least
PROFILE = "caldot1"


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float),
          flush=True)


def peak_bytes(device=None):
    import jax
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


# ---------------------------------------------------------------------------
# Set-up: bank, θ and clips from seed 0
# ---------------------------------------------------------------------------

def build_system(cfg, *, n_clips: int = 4, n_frames: int = 64,
                 det_steps: int = 400, proxy_steps: int = 80,
                 seed: int = 0) -> dict:
    """Train the detector and the proxy, calibrate the detector's
    confidence and the proxy's threshold, and return the bank, θ and
    clips.  The first two clips train the models and carry phases
    1-3.

    The detector is ``ssd-deep``: its receptive field (91 px) spans the
    48-86 px vehicles of a 960x544 frame, while ``ssd-lite``'s (31 px)
    sees only their flat interiors and fires on every cell inside
    them.  The planner's per-size detector times come from the area
    model ``windows.detector_time_model`` rather than from timing the
    chip (time in proportion to the window's area, the paper's cost
    premise), so the window plan, and with it the path each phase
    drives, does not depend on host timing noise."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import pipeline as pl
    from repro.core.detector import STRIDE
    from repro.core.proxy import (ProxyModel, calibrate_threshold,
                                  cells_from_detections, proxy_loss)
    from repro.core.tracker import init_tracker
    from repro.core.train_models import _fit, detector_f1, train_detector
    from repro.core.windows import detector_time_model
    from repro.data.video_synth import make_split

    t0 = time.perf_counter()
    clips = make_split(PROFILE, "train", n_clips, n_frames=n_frames)
    det_res = cfg.detector.resolutions[0]
    det, losses = train_detector("ssd-deep", clips[:2], [det_res],
                                 steps=det_steps, seed=seed)
    bank = pl.ModelBank(cfg, {det.arch: det})
    grid = pl.det_grid(det_res)
    bank.sizes_cells = [grid, (grid[0] // 4, grid[1] // 4),
                        (grid[0] // 2, grid[1] // 2)][:cfg.windows.k]
    bank.ref_grid = grid
    bank.tracker_params = init_tracker(cfg.tracker, seed=seed)
    area_time = detector_time_model(grid, 1.0, overhead_frac=0.0)
    bank.win_times.update({(det.arch, s): area_time(s)
                           for s in bank.sizes_cells})

    # detector confidence: the menu value with the best F1 against the
    # training clips' ground truth; its detections on every other frame
    # then label the proxy (θ_best labels)
    f1 = {c: detector_f1(det, clips[:2], det_res, c, n_frames=16)
          for c in cfg.detector.confidences}
    conf = max(f1, key=f1.get)
    W, H = det_res
    frames = np.stack([pl.render_frame(c, f, W, H)[0] for c in clips[:2]
                       for f in range(0, c.n_frames, 2)])
    dets = det.detect_batch_bucketed(frames, conf)
    gt = [len(c.boxes_at(f)) for c in clips[:2]
          for f in range(0, c.n_frames, 2)]

    res = cfg.proxy.resolutions[0]
    proxy = ProxyModel(cfg.proxy.cell, cfg.proxy.base_channels, res,
                       seed=seed)
    hc, wc = proxy.grid_shape()
    small = pl.downsample_chunk(frames, res)
    labels = np.stack([cells_from_detections(d, hc, wc) for d in dets])
    held = np.arange(len(frames)) % 4 == 0       # calibration frames
    fr, lb = small[~held], labels[~held]
    rng = np.random.default_rng(seed)

    def batches():
        for _ in range(proxy_steps):
            idx = rng.integers(len(fr), size=8)
            yield (jnp.asarray(fr[idx]), jnp.asarray(lb[idx]))

    proxy.params, _ = _fit(
        lambda p, f_, l_: proxy_loss(p, f_, l_, cfg.proxy.cell),
        proxy.params, batches(), lr=3e-3)
    bank.proxies = {res: proxy}
    scores, _ = proxy.scores_batch(small[held], 0.5)
    threshold = calibrate_threshold(list(scores), list(labels[held]),
                                    cfg.proxy.thresholds, min_recall=0.9)
    params = pl.PipelineParams(
        det.arch, det_res, conf, gap=1, proxy_res=res,
        proxy_threshold=threshold, tracker="recurrent", refine=False)
    setup_s = time.perf_counter() - t0
    emit("setup", setup_cold_s=setup_s, frame=[W, H], proxy=list(res),
         detector=det.arch, detector_stride_px=STRIDE, cell_px=pl.CELL_PX,
         config_stride_px=cfg.detector.stride,
         window_sizes_cells=[list(s) for s in bank.sizes_cells],
         det_steps=det_steps, det_loss_last=float(np.mean(losses[-10:])),
         f1_by_conf=f1, det_conf=conf,
         dets_per_frame=float(np.mean([len(d) for d in dets])),
         gt_per_frame=float(np.mean(gt)),
         label_positive_frac=float(labels.mean()),
         proxy_threshold=threshold, clips=n_clips, frames=n_frames)
    return {"cfg": cfg, "bank": bank, "params": params, "clips": clips}


# ---------------------------------------------------------------------------
# The check shared by phases 1-3
# ---------------------------------------------------------------------------

def compare(results, refs, clips) -> dict:
    """MOTA of each run against the clip's ground truth must be within
    ``MOTA_TOL`` of the comparison run's; the runs together must hold
    a detection and a track of ``MIN_TRACK`` frames.  Bit-identity and
    the largest box difference are findings, not gates."""
    import numpy as np
    from repro.core.metrics import mota

    motas, identical, box_diff = [], True, 0.0
    for r, ref, clip in zip(results, refs, clips):
        m, m_ref = mota(r.tracks, clip), mota(ref.tracks, clip)
        assert abs(m - m_ref) <= MOTA_TOL, \
            f"clip {clip.clip_id}: MOTA {m} vs {m_ref}"
        motas.append([m, m_ref])
        same = len(r.tracks) == len(ref.tracks) and all(
            a.shape == b.shape for a, b in zip(r.tracks, ref.tracks))
        identical &= same and all(np.array_equal(a, b)
                                  for a, b in zip(r.tracks, ref.tracks))
        if same and box_diff is not None:
            for a, b in zip(r.tracks, ref.tracks):
                if len(a):
                    box_diff = max(box_diff, float(
                        np.abs(a[:, 1:5] - b[:, 1:5]).max()))
        else:
            box_diff = None                  # structures differ
    dets = sum(len(t) for r in results for t in r.tracks)
    longest = max((len(t) for r in results for t in r.tracks), default=0)
    assert dets >= 1, "no detection reached a track"
    assert longest >= MIN_TRACK, f"longest track {longest} frames"
    return {"mota": motas, "bit_identical": identical,
            "max_box_diff": box_diff, "track_rows": dets,
            "longest_track": longest}


def _dispatches(results) -> dict:
    out: dict = {}
    for r in results:
        for k, v in (r.dispatches or {}).items():
            out[k] = out.get(k, 0) + v
    return out


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def phase_extraction(sys_: dict) -> list:
    from repro.core import pipeline as pl
    from repro.core.detector import detect_jit_entries
    bank, params, clips = sys_["bank"], sys_["params"], sys_["clips"][:2]
    det = bank.detectors[params.det_arch]
    t0 = time.perf_counter()
    det.dispatches = 0
    got = [pl.run_clip(bank, params, c) for c in clips]
    stream_disp = det.dispatches
    det.dispatches = 0
    ref = [pl.run_clip_frames(bank, params, c) for c in clips]
    cold = time.perf_counter() - t0
    fields = compare(got, ref, clips)
    full = sum(r.full_frames for r in got)
    gathered = sum(r.detector_windows for r in got) - full
    assert gathered > 0, "no sub-frame window was gathered"
    emit("extraction", **fields,
         dispatches=_dispatches(got), detector_dispatches=stream_disp,
         reference_detector_dispatches=det.dispatches,
         gathered_windows=gathered, full_frames=full,
         skipped_frames=sum(r.skipped_frames for r in got),
         detect_jit_entries=detect_jit_entries(),
         cold_s=cold, peak_bytes=peak_bytes(), **gather_memory(sys_))
    return got


def gather_memory(sys_: dict) -> dict:
    """One chunk's window gather at the smallest window size with a full
    16-row table: its compiled temp bytes, and the device's peak bytes
    after running it once on zeros (to see whether the temp is
    allocated at run time)."""
    import jax
    import jax.numpy as jnp
    from repro.core.executor import DEFAULT_CHUNK
    from repro.core.pipeline import CELL_PX
    from repro.kernels.window_gather import window_gather_batch
    W, H = sys_["params"].det_res
    wc, hc = min(sys_["bank"].sizes_cells)
    frames = jnp.zeros((DEFAULT_CHUNK, H, W, 3), jnp.float32)
    tbl = jnp.zeros((DEFAULT_CHUNK, 3), jnp.int32)
    compiled = window_gather_batch.lower(
        frames, tbl, win_h=hc * CELL_PX, win_w=wc * CELL_PX,
        cell=CELL_PX).compile()
    compiled(frames, tbl).block_until_ready()
    return {"gather_temp_bytes":
            int(compiled.memory_analysis().temp_size_in_bytes),
            "peak_bytes_after_gather": peak_bytes()}


def phase_device_track(sys_: dict, host: list) -> list:
    from repro.core.executor import ExecutorOptions, run_clip_streamed
    bank, params, clips = sys_["bank"], sys_["params"], sys_["clips"][:2]
    t0 = time.perf_counter()
    got = [run_clip_streamed(bank, params, c,
                             ExecutorOptions(device_tracker=True))
           for c in clips]
    emit("device_track", **compare(got, host, clips),
         dispatches=_dispatches(got), cold_s=time.perf_counter() - t0,
         peak_bytes=peak_bytes())
    return got


def phase_fleet(sys_: dict, solo: list, n_streams: int = 4) -> list:
    """``n_streams`` threads, stream i running clip i % 2, all sharing
    one ``BatchBroker`` and one ``TrackBroker``."""
    from repro.core.executor import (BatchBroker, ExecutorOptions,
                                     TrackBroker, run_clip_streamed)
    bank, params, clips = sys_["bank"], sys_["params"], sys_["clips"][:2]
    batch, track = BatchBroker(), TrackBroker()
    results: list = [None] * n_streams
    errors: list = []

    def stream(i: int) -> None:
        try:
            opts = ExecutorOptions(prefetch=False, batch_broker=batch,
                                   track_broker=track, device_assign=True)
            results[i] = run_clip_streamed(bank, params,
                                           clips[i % len(clips)], opts)
        except BaseException as exc:      # re-raised on the main thread
            errors.append(exc)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=stream, args=(i,))
               for i in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batch.close()
    track.close()
    if errors:
        raise errors[0]
    emit("fleet", streams=n_streams,
         **compare(results, [solo[i % len(clips)] for i in range(n_streams)],
                   [clips[i % len(clips)] for i in range(n_streams)]),
         detect_broker_dispatches=batch.dispatches,
         detect_windows_in=batch.windows_in,
         track_broker_dispatches=track.dispatches,
         track_steps_in=track.steps_in, cold_s=time.perf_counter() - t0,
         peak_bytes=peak_bytes())
    return results


def _query(region=None, time_range=None, min_len=2, min_count=1,
           limit=None, aggregate="frames"):
    from repro.query import (CountAtLeast, Limit, Query, Region,
                             TimeRange, TrackFilter)
    where = [TrackFilter(min_len=min_len), CountAtLeast(min_count)]
    if region is not None:
        where.append(Region(*region))
    if time_range is not None:
        where.append(TimeRange(*time_range))
    return Query(tuple(where), None if limit is None else Limit(*limit),
                 aggregate)


QUERIES = (
    dict(aggregate="count"),
    dict(region=(0.0, 0.0, 0.5, 0.5)),
    dict(min_count=2, limit=(5, 8)),
    dict(time_range=(0, 32), aggregate="tracks"),
    dict(min_len=4, aggregate="duration"),
)


def check_queries(service, store, clips) -> int:
    """Every query of ``QUERIES`` over ``clips`` must answer exactly as
    ``reference_query`` over the store's tracks."""
    from repro.query.ref import reference_query
    tracks = [store.tracks(c) for c in clips]
    fps = [c.profile.fps for c in clips]
    for kw in QUERIES:
        got = service.query(_query(**kw), clips)
        ref = reference_query(tracks, fps, **kw)
        assert got.frames == ref["frames"], kw
        assert got.aggregates == ref["aggregates"], kw
    return len(QUERIES)


def phase_store(sys_: dict, segment: int = 24) -> None:
    """Batch ingest of two clips, live appends of a third, and the
    query mix against the reference at a mid-stream watermark and
    after the seal."""
    from repro.query import QueryService, TrackStore
    from repro.stream import SegmentIngestor
    bank, params, clips = sys_["bank"], sys_["params"], sys_["clips"]
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_store_") as root:
        store = TrackStore(root, bank, params)
        report = store.ingest(clips[:2])
        assert report.ingested == 2, report
        service = QueryService(store)
        live = clips[2]
        ingestor = SegmentIngestor(store, service=service)
        assert ingestor.open(live) == 0
        rep = ingestor.append(live, segment)
        assert rep.watermark == min(segment, live.n_frames), rep
        answered = check_queries(service, store, [live])
        ingestor.seal(live)
        assert store.watermark(live) == live.n_frames, \
            f"sealed at {store.watermark(live)} of {live.n_frames} frames"
        answered += check_queries(service, store, clips[:3])
        rows = sum(len(t) for c in clips[:3] for t in store.tracks(c))
        assert rows, "the store holds no track to query"
    emit("store", ingested=report.ingested, live_segment=segment,
         queries_exact=answered, track_rows=rows,
         cold_s=time.perf_counter() - t0, peak_bytes=peak_bytes())


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------

def phase_multichip(sys_: dict) -> None:
    """``run_clips`` with the default round-robin of chunks over
    ``jax.local_devices()``, every device must receive chunks, and each
    clip must match the same clip pinned to one device.  Then the
    ``ExecutorOptions.mesh`` path: on a TPU it must refuse at
    construction, naming what is missing; elsewhere it runs under the
    same comparison."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import executor as ex
    from repro.kernels import use_pallas

    bank, params, clips = sys_["bank"], sys_["params"], sys_["clips"]
    devices = jax.local_devices()
    used: dict = {}
    device_for = ex._RunContext.device_for

    def counting_device_for(ctx, task):
        dev = device_for(ctx, task)
        used.setdefault(dev.id, set()).add((ctx.stream, task.index))
        return dev

    t0 = time.perf_counter()
    ex._RunContext.device_for = counting_device_for
    try:
        spread, _ = ex.run_clips(bank, params, clips)
    finally:
        ex._RunContext.device_for = device_for
    chunks = {dev: len(c) for dev, c in sorted(used.items())}
    assert sorted(chunks) == sorted(d.id for d in devices), \
        f"chunks per device {chunks}"
    pinned, _ = ex.run_clips(bank, params, clips,
                             ex.ExecutorOptions(devices=[jax.devices()[0]]))
    fields = compare(spread, pinned, clips)

    mesh = Mesh(np.array(devices).reshape(len(devices), 1),
                ("data", "model"))
    opts = ex.ExecutorOptions(mesh=mesh)
    if use_pallas():
        try:
            ex.ClipExecutor(bank, params, opts)
        except NotImplementedError as exc:
            mesh_result = f"refused at construction: {exc}"
        else:
            raise AssertionError("mesh executor constructed on a TPU")
    else:
        meshed, _ = ex.run_clips(bank, params, clips, opts)
        compare(meshed, pinned, clips)
        mesh_result = "ran; matches the pinned runs"
    emit("multichip", devices=len(devices), chunks_per_device=chunks,
         **fields, dispatches=_dispatches(spread), mesh=mesh_result,
         cold_s=time.perf_counter() - t0,
         peak_bytes={d.id: peak_bytes(d) for d in devices})


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.core.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    from repro.configs.multiscope import MULTISCOPE_PIPELINE
    emit("start", compile_cache=cache, jax=jax.__version__)
    sys_ = build_system(MULTISCOPE_PIPELINE)
    if args.chips == 4:
        phase_multichip(sys_)
    else:
        host = phase_extraction(sys_)
        phase_device_track(sys_, host)
        phase_fleet(sys_, host)
        phase_store(sys_)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
