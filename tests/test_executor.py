"""Equivalence tests for the streaming clip executor: bit-identical
tracks and decode-ledger counters vs the per-frame reference path for
every chunk size / scheduler / prefetch setting, plus the tuner's
chunk-size (scheduler module) proposal path."""
import dataclasses

import numpy as np
import pytest

from repro.configs.multiscope import MULTISCOPE_PIPELINE
from repro.core import pipeline as pl
from repro.core import tuner as tuner_mod
from repro.core.executor import (DEFAULT_CHUNK, ClipExecutor,
                                 DecodePool, ExecutorOptions,
                                 TrackBroker, effective_chunk,
                                 run_clip_streamed, run_clips)
from repro.core.proxy import ProxyModel
from repro.core.tracker import init_tracker
from repro.core.train_models import train_detector
from repro.data.video_synth import make_split


@pytest.fixture(scope="module")
def exec_bank():
    cfg = MULTISCOPE_PIPELINE.reduced()
    clips = make_split("caldot1", "train", 2, n_frames=24)
    det, _ = train_detector("ssd-lite", clips,
                            [cfg.detector.resolutions[-1]], steps=60)
    bank = pl.ModelBank(cfg, {"ssd-lite": det, "ssd-deep": det})
    res = cfg.proxy.resolutions[-1]
    proxy = ProxyModel(cfg.proxy.cell, cfg.proxy.base_channels, res)
    bank.proxies = {res: proxy}
    bank.sizes_cells = [pl.det_grid(cfg.detector.resolutions[-1]),
                        (3, 2), (5, 3)]
    bank.ref_grid = pl.det_grid(cfg.detector.resolutions[-1])
    bank.tracker_params = init_tracker(cfg.tracker)
    # a threshold just above the untrained proxy's score median makes
    # the positive-cell grid SPARSE, so planning emits real sub-frame
    # windows (the interesting path for the gather/upload machinery)
    W, H = cfg.detector.resolutions[-1]
    frame, _ = pl.render_frame(clips[0], 0, W, H)
    s, _ = proxy.scores(pl._downsample(frame, res))
    return bank, clips, res, float(np.quantile(s, 0.85))


def _assert_same(a, b):
    """Tracks bit-identical; decode-ledger counters equal."""
    assert a.frames_processed == b.frames_processed
    assert a.detector_windows == b.detector_windows
    assert a.full_frames == b.full_frames
    assert a.skipped_frames == b.skipped_frames
    assert len(a.tracks) == len(b.tracks)
    for x, y in zip(a.tracks, b.tracks):
        np.testing.assert_array_equal(x, y)


def _params(bank, res, th, **kw):
    base = dict(det_arch="ssd-lite",
                det_res=bank.cfg.detector.resolutions[-1],
                det_conf=0.4, gap=1, proxy_res=res, proxy_threshold=th,
                tracker="sort", refine=False)
    base.update(kw)
    return pl.PipelineParams(**base)


# 24-frame clips at gap=1: B=1 degenerates to per-frame chunks, B=7
# leaves a trailing partial chunk of 3, B=16 leaves one of 8, B=33
# exceeds the clip (single partial chunk)
@pytest.mark.parametrize("chunk", [1, 7, 16, 33])
@pytest.mark.parametrize("prefetch", [False, True])
def test_executor_equivalence_chunk_sizes(exec_bank, chunk, prefetch):
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=chunk)
    opts = ExecutorOptions(prefetch=prefetch)
    for clip in clips:
        _assert_same(pl.run_clip_frames(bank, params, clip),
                     run_clip_streamed(bank, params, clip, opts))


def test_executor_prefetch_recurrent(exec_bank):
    """The recurrent tracker under the streaming scheduler: chunked
    crop embeddings + prefetch must reproduce the per-frame path
    bit-exactly."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, tracker="recurrent")
    for clip in clips:
        _assert_same(pl.run_clip_frames(bank, params, clip),
                     run_clip_streamed(bank, params, clip))


def test_executor_empty_detections_clip(exec_bank):
    """Impossible proxy threshold: every frame skipped, zero detections
    anywhere — the executor must agree with the reference on the empty
    case too (no stray uploads, no tracker steps with stale state)."""
    bank, clips, res, _ = exec_bank
    params = _params(bank, res, 0.9999999, gap=2, chunk_size=7)
    r = run_clip_streamed(bank, params, clips[0])
    assert r.skipped_frames == r.frames_processed
    assert all(len(t) == 0 for t in r.tracks)
    _assert_same(pl.run_clip_frames(bank, params, clips[0]), r)


def test_executor_run_clips_matches_per_clip(exec_bank):
    """The multi-clip sweep (cross-clip decode prefetch, per-clip device
    offsets) returns exactly the per-clip results in order."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, tracker="recurrent")
    results, total = run_clips(bank, params, clips)
    assert len(results) == len(clips)
    for clip, r in zip(clips, results):
        _assert_same(pl.run_clip_frames(bank, params, clip), r)
    assert total == pytest.approx(sum(r.seconds for r in results))


# ---------------------------------------------------------------------------
# DETECT's host batch: the chunk's own rows or the reused staging buffer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def thresholds(exec_bank):
    """Proxy thresholds by plan mix: ``mixed`` plans full frames, sub-
    frame windows and skips within one chunk, ``sparse`` is the
    fixture's calibrated threshold, ``dense`` plans every frame full."""
    bank, clips, res, th = exec_bank
    W, H = bank.cfg.detector.resolutions[-1]
    frame, _ = pl.render_frame(clips[0], 0, W, H)
    s, _ = bank.proxies[res].scores(pl._downsample(frame, res))
    return {"mixed": float(np.quantile(s, 0.8)), "sparse": th,
            "dense": float("-inf")}


def _full_frame_batches(monkeypatch):
    """Record every full-frame batch DETECT builds: (chunk rows, slots,
    path, bucket), after checking that it holds the slots' frames and
    zeros below them, as a fresh padded gather would."""
    from repro.core import executor as ex
    made = []
    build = ex._RunContext.full_frame_batch

    def spy(ctx, frames, slots):
        batch, path = build(ctx, frames, slots)
        n = len(slots)
        np.testing.assert_array_equal(batch[:n], frames[slots])
        assert not batch[n:].any()
        made.append((len(frames), list(slots), path, len(batch)))
        return batch, path
    monkeypatch.setattr(ex._RunContext, "full_frame_batch", spy)
    return made


def _per_frame_dets(monkeypatch):
    """Record each run's per-frame detections, in frame order: the
    reference's from ``detect_with_windows``, the executor's from its
    DETECT stage -> {clip_id: [dets]}."""
    from repro.core import executor as ex
    ref: dict = {}
    got: dict = {}
    detect_one = pl.detect_with_windows
    current: list = []

    def ref_detect(*args, **kw):
        dets, windows = detect_one(*args, **kw)
        current.append(dets)
        return dets, windows

    def stage(ctx, task):
        out = ex.stage_detect(ctx, task)
        got.setdefault(ctx.clip.clip_id, []).extend(out.dets)
        return out
    monkeypatch.setattr(pl, "detect_with_windows", ref_detect)
    monkeypatch.setitem(ex.DEFAULT_STAGES, "detect", stage)

    def reference(bank, params, clip):
        current.clear()
        r = pl.run_clip_frames(bank, params, clip)
        ref[clip.clip_id] = list(current)
        return r
    return reference, ref, got


def _leading(slots):
    return slots == list(range(len(slots)))


# what each case must exercise, read from the batches DETECT built
HOST_BATCH_CASES = {
    # full-frame slots with gaps between them: skipped frames and
    # sub-frame windows in the same chunk; the staged gather
    "non_contiguous": lambda made, B: any(
        not _leading(s) for _, s, _, _ in made),
    # the clip's last chunk is short (24 frames at B=7 or 16)
    "partial": lambda made, B: any(rows < B for rows, _, _, _ in made),
    # a staged bucket smaller than the one before it, with padding
    # rows where the earlier batch left its frames
    "shrinking": lambda made, B: any(
        b < pb and len(s) < b for (_, _, pp, pb), (_, s, p, b)
        in zip(made, made[1:]) if pp == p == "staged"),
}


@pytest.mark.parametrize("mix,chunk,case", [
    ("mixed", 4, "non_contiguous"),
    ("mixed", 6, "non_contiguous"),
    ("sparse", 7, "non_contiguous"),
    ("sparse", 16, "non_contiguous"),
    ("mixed", 7, "partial"),
    ("mixed", 16, "partial"),
    ("dense", 16, "partial"),
    ("dense", 7, "shrinking"),
    ("mixed", 16, "shrinking"),
])
def test_run_clips_host_batch_matches_per_frame(
        exec_bank, thresholds, monkeypatch, mix, chunk, case):
    """``run_clips`` over several clips, with DETECT's full-frame batch
    taken from the chunk's rows or gathered into the staging buffer
    that persists across chunks and clips: tracks, counters and every
    frame's detections are bit-identical to the per-frame path."""
    bank, clips, res, _ = exec_bank
    params = _params(bank, res, thresholds[mix], chunk_size=chunk,
                     tracker="recurrent")
    made = _full_frame_batches(monkeypatch)
    reference, ref, got = _per_frame_dets(monkeypatch)
    results, _ = run_clips(bank, params, clips)
    assert HOST_BATCH_CASES[case](made, chunk), made
    for clip, r in zip(clips, results):
        _assert_same(reference(bank, params, clip), r)
        want, have = ref[clip.clip_id], got[clip.clip_id]
        assert len(want) == len(have)
        for a, b in zip(want, have):
            np.testing.assert_array_equal(a, b)


def test_run_clips_allocates_one_host_batch(exec_bank, thresholds):
    """With tracing on, every full-frame ``detect.upload`` names its
    path, both paths occur, and the whole ``run_clips`` call allocates
    one staging buffer of ``next_bucket(B)`` frames, on the first
    staged chunk, and no other host batch (whole chunks go up as
    decoded)."""
    from repro.core.detector import next_bucket
    from repro.obs.trace import TRACER
    bank, clips, res, _ = exec_bank
    B = 4
    params = _params(bank, res, thresholds["mixed"], chunk_size=B)
    TRACER.enable()
    TRACER.clear()
    try:
        run_clips(bank, params, clips)
        spans = TRACER.snapshot()
    finally:
        TRACER.disable()
        TRACER.clear()
    W, H = params.det_res
    full = [s for s in spans
            if s.name == "detect.upload" and "path" in (s.args or {})]
    assert {s.args["path"] for s in full} == {"direct", "staged"}
    assert all("alloc_bytes" in s.args for s in full)
    allocs = [s.args["alloc_bytes"] for s in spans
              if s.args and s.args.get("alloc_bytes")]
    assert allocs == [next_bucket(B) * H * W * 3 * 4]
    first = next(s for s in full if s.args["path"] == "staged")
    assert first.args["alloc_bytes"] == allocs[0]


def test_staging_buffer_per_thread():
    """One buffer per draining thread, allocated once and reused: two
    threads draining runs of one executor never share it."""
    import threading
    from repro.core.executor import _Staging
    staging = _Staging()
    a = staging.buffer((4, 2, 3, 3))
    assert a.shape == (4, 2, 3, 3) and a.dtype == np.float32
    assert staging.buffer((4, 2, 3, 3)) is a
    other = []
    t = threading.Thread(target=lambda: other.append(
        staging.buffer((4, 2, 3, 3))))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and other[0] is not a
    assert other[0].shape == a.shape


def test_executor_mesh_sharded_upload(exec_bank):
    """Chunk uploads through LogicalRules mesh sharding (batch axis on
    the data axis) stay bit-identical."""
    from repro.launch.mesh import make_host_mesh
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th)
    opts = ExecutorOptions(mesh=make_host_mesh(1, 1))
    _assert_same(pl.run_clip_frames(bank, params, clips[0]),
                 run_clip_streamed(bank, params, clips[0], opts))


def test_run_clip_streaming_dispatch(exec_bank):
    """pipeline.run_clip routes to the streaming executor by default;
    all three engines agree."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, gap=2)
    a = pl.run_clip(bank, params, clips[0])
    _assert_same(a, pl.run_clip(bank, params, clips[0],
                                engine="chunked"))
    _assert_same(a, pl.run_clip(bank, params, clips[0], engine="frame"))
    with pytest.raises(ValueError):
        pl.run_clip(bank, params, clips[0], engine="nope")


def test_executor_stage_failure_propagates(exec_bank):
    """A stage exception mid-stream must propagate promptly: the decode
    worker is blocked in q.put on the full bounded queue when the
    failure hits, and drain has to unblock it before re-raising (a bare
    join would deadlock forever)."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=1)   # chunks >> depth

    def boom(ctx, task):
        raise RuntimeError("detect failed")

    ex = ClipExecutor(bank, params, ExecutorOptions(prefetch=True),
                      stages={"detect": boom})
    with pytest.raises(RuntimeError, match="detect failed"):
        ex.run(clips[0])


def test_executor_cancel_releases_started_run(exec_bank):
    """run_clips starts clip i+1's decode ahead; an abandoned run must
    be cancellable without draining it (its worker would otherwise
    block forever holding decoded chunks)."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=1)
    ex = ClipExecutor(bank, params, ExecutorOptions(prefetch=True,
                                                    decode_workers=2))
    run = ex.start(clips[0])
    ex.cancel(run)                       # must return, not hang
    _, worker_threads, _, _ = run.handle
    assert not any(t.is_alive() for t in worker_threads)


def test_effective_chunk_resolution():
    p = pl.PipelineParams("ssd-lite", (128, 80), 0.4)
    assert effective_chunk(p) == DEFAULT_CHUNK
    assert effective_chunk(dataclasses.replace(p, chunk_size=32)) == 32
    assert effective_chunk(dataclasses.replace(p, chunk_size=32),
                           override=8) == 8


# ---------------------------------------------------------------------------
# Decode worker pool (ExecutorOptions.decode_workers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workers", [2, 3])
def test_executor_decode_worker_pool(exec_bank, workers):
    """N decode workers + the reorder gate must reproduce the
    single-thread schedule bit-exactly (chunks reach TRACK in frame
    order regardless of which worker decoded them first)."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=4)   # several chunks
    opts = ExecutorOptions(prefetch=True, prefetch_depth=2,
                           decode_workers=workers)
    for clip in clips:
        _assert_same(pl.run_clip_frames(bank, params, clip),
                     run_clip_streamed(bank, params, clip, opts))


def test_run_clips_decode_worker_pool(exec_bank):
    """The pool option threads through the multi-clip sweep."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=4)
    results, _ = run_clips(bank, params, clips,
                           ExecutorOptions(decode_workers=2))
    for clip, r in zip(clips, results):
        _assert_same(pl.run_clip_frames(bank, params, clip), r)


def test_executor_pool_failure_propagates(exec_bank):
    """A mid-stream stage failure with a worker pool: every worker —
    including ones parked at the reorder gate or blocked on the full
    queue — must be released before the error propagates."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=1)   # chunks >> depth

    def boom(ctx, task):
        raise RuntimeError("detect failed")

    ex = ClipExecutor(bank, params,
                      ExecutorOptions(prefetch=True, decode_workers=3),
                      stages={"detect": boom})
    with pytest.raises(RuntimeError, match="detect failed"):
        ex.run(clips[0])
    # all pool threads must have exited (no leaked decoders)
    import threading as _t
    assert not [t for t in _t.enumerate()
                if t.name.startswith("multiscope-decode")
                and t.is_alive()]


# ---------------------------------------------------------------------------
# Shared decode pool (one pool across the in-flight clips of run_clips)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pool_size", [1, 3])
def test_run_clips_shared_pool_bit_identical(exec_bank, pool_size):
    """One DecodePool shared by the two in-flight clips: per-clip
    reorder gates must keep TRACK frame-ordered, so tracks stay
    bit-identical to the per-frame reference for any pool size."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=4)
    pool = DecodePool(pool_size)
    try:
        results, _ = run_clips(bank, params, clips,
                               ExecutorOptions(decode_pool=pool))
        for clip, r in zip(clips, results):
            _assert_same(pl.run_clip_frames(bank, params, clip), r)
        # an external pool is reusable across sweeps
        results2, _ = run_clips(bank, params, clips,
                                ExecutorOptions(decode_pool=pool))
        for a, b in zip(results, results2):
            _assert_same(a, b)
    finally:
        pool.close()


def test_run_clips_owns_pool_by_default(exec_bank):
    """run_clips with default options creates (and closes) its own
    shared pool; no pool threads may leak."""
    import threading as _t
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=4)
    results, _ = run_clips(bank, params, clips)
    for clip, r in zip(clips, results):
        _assert_same(pl.run_clip_frames(bank, params, clip), r)
    assert not [t for t in _t.enumerate()
                if t.name.startswith("multiscope-pool-decode")
                and t.is_alive()]


def test_shared_pool_failure_releases_workers(exec_bank):
    """A stage failure mid-stream under the shared pool: the error
    propagates, the pool's workers survive (they are shared), and the
    pool still closes cleanly."""
    import threading as _t
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=1)   # chunks >> depth

    def boom(ctx, task):
        raise RuntimeError("detect failed")

    pool = DecodePool(2)
    try:
        ex = ClipExecutor(bank, params,
                          ExecutorOptions(decode_pool=pool),
                          stages={"detect": boom})
        with pytest.raises(RuntimeError, match="detect failed"):
            ex.run(clips[0])
        # workers are still alive and serviceable after the failure
        ex_ok = ClipExecutor(bank, params,
                             ExecutorOptions(decode_pool=pool))
        _assert_same(pl.run_clip_frames(bank, params, clips[0]),
                     ex_ok.run(clips[0]))
    finally:
        pool.close()
    assert not [t for t in _t.enumerate()
                if t.name.startswith("multiscope-pool-decode")
                and t.is_alive()]


def test_executor_segment_resume_hooks(exec_bank):
    """start(frame_ids=..., tracker=...): running a clip as two
    resumed slices reproduces the one-shot run bit-exactly (the hook
    repro.stream builds on)."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, tracker="recurrent", gap=2)
    clip = clips[0]
    ref = pl.run_clip_frames(bank, params, clip)
    ex = ClipExecutor(bank, params)
    ids = list(range(0, clip.n_frames, params.gap))
    cut = len(ids) // 2
    from repro.core.tracker import RecurrentTracker
    tracker = RecurrentTracker(bank.cfg.tracker, bank.tracker_params)
    r1 = ex.finish(ex.start(clip, frame_ids=ids[:cut], tracker=tracker))
    r2 = ex.finish(ex.start(clip, frame_ids=ids[cut:], tracker=tracker))
    assert r1.frames_processed + r2.frames_processed \
        == ref.frames_processed
    assert r1.detector_windows + r2.detector_windows \
        == ref.detector_windows
    assert len(ref.tracks) == len(r2.tracks)
    for a, b in zip(ref.tracks, r2.tracks):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Device-resident TRACK: per-step device assignment, chunk-scan tracker,
# cross-stream track batching
# ---------------------------------------------------------------------------

def test_executor_stage_seconds_and_dispatches(exec_bank):
    """RunResult carries per-stage wall/process seconds and dispatch
    counts for every named stage, and they are internally consistent
    (non-negative, process <= a generous multiple of wall)."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, tracker="recurrent", chunk_size=7)
    r = run_clip_streamed(bank, params, clips[0])
    assert r.stage_seconds is not None
    assert set(r.stage_seconds) == {"decode", "proxy", "detect", "track"}
    for s, d in r.stage_seconds.items():
        assert d["wall"] >= 0.0 and d["process"] >= 0.0, (s, d)
    assert r.dispatches is not None
    assert set(r.dispatches) == {"proxy", "detect", "track"}
    assert r.dispatches["proxy"] > 0
    assert r.dispatches["track"] > 0


def test_executor_device_assign_roundtrip(exec_bank):
    """ExecutorOptions(device_assign=True) routes the recurrent
    tracker's per-step association through the fused track-step kernel
    and reproduces the host path bit-exactly; the flag round-trips to
    the tracker and the device steps are counted."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, tracker="recurrent", chunk_size=7)
    for clip in clips:
        ref = run_clip_streamed(bank, params, clip)
        ex = ClipExecutor(bank, params,
                          ExecutorOptions(device_assign=True))
        run = ex.start(clip)
        assert getattr(run.ctx.tracker, "assign", None) == "device"
        dev = ex.finish(run)
        _assert_same(ref, dev)
        # every chunk embeds once; device steps add per-frame dispatches
        assert dev.dispatches["track"] > ref.dispatches["track"]


@pytest.mark.parametrize("chunk", [1, 16])
def test_executor_device_tracker_equivalence(exec_bank, chunk):
    """device_tracker=True executes whole chunks as one scan dispatch
    and stays bit-identical to the host tracker for any chunking."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, tracker="recurrent",
                     chunk_size=chunk)
    for clip in clips:
        ref = run_clip_streamed(bank, params, clip)
        dev = run_clip_streamed(bank, params, clip,
                                ExecutorOptions(device_tracker=True))
        _assert_same(ref, dev)


def test_track_broker_multi_stream_bit_identical(exec_bank):
    """Two concurrent streams sharing a TrackBroker: per-frame device
    track steps coalesce into batched dispatches, results stay
    bit-identical per stream, and the broker's ledger accounts for
    every step."""
    import threading
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, tracker="recurrent", chunk_size=7)
    broker = TrackBroker(linger_ms=2.0)
    opts = ExecutorOptions(device_assign=True, track_broker=broker)
    ex = ClipExecutor(bank, params, opts)
    out = [None] * len(clips)

    def run(i):
        out[i] = ex.run(clips[i])

    ts = [threading.Thread(target=run, args=(i,))
          for i in range(len(clips))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    broker.close()
    for i, clip in enumerate(clips):
        _assert_same(run_clip_streamed(bank, params, clip), out[i])
    assert 0 < broker.dispatches <= broker.steps_in
    # one fill entry per dispatch; their sum is every step admitted
    assert len(broker.stream_fill) == broker.dispatches
    assert sum(broker.stream_fill) == broker.steps_in


# ---------------------------------------------------------------------------
# The tuner's scheduler module (chunk-size proposals)
# ---------------------------------------------------------------------------

def test_tuner_chunk_proposal_gating():
    p = pl.PipelineParams("ssd-lite", (128, 80), 0.4, gap=1)
    # dense full-frame θ: nothing to amortize
    assert tuner_mod.propose_chunk(p) is None
    # sparse θ (gap >= 2): double B from the default
    sparse = dataclasses.replace(p, gap=4)
    c = tuner_mod.propose_chunk(sparse)
    assert c is not None and c.chunk_size == 2 * DEFAULT_CHUNK
    # proxy-gated θ proposes too
    gated = dataclasses.replace(p, proxy_res=(32, 24))
    assert tuner_mod.propose_chunk(gated).chunk_size == 2 * DEFAULT_CHUNK
    # doubling continues from θ's current B and stops at the ceiling
    c2 = tuner_mod.propose_chunk(dataclasses.replace(sparse,
                                                     chunk_size=32))
    assert c2.chunk_size == 64
    assert tuner_mod.propose_chunk(
        dataclasses.replace(sparse, chunk_size=64)) is None


def test_tuner_chunk_proposal_accuracy_neutral(exec_bank):
    """The chunk-size-tuning path end to end: a scheduler-module
    candidate evaluated through the tuner must reproduce the current
    θ's accuracy exactly (tracks are bit-identical across B), so it can
    only ever win on the runtime tiebreak."""
    bank, clips, res, th = exec_bank
    cur = _params(bank, res, th, gap=2)
    cand = tuner_mod.propose_chunk(cur)
    assert cand is not None and cand != cur
    acc_cur, _ = tuner_mod._evaluate(bank, cur, clips)
    acc_cand, _ = tuner_mod._evaluate(bank, cand, clips)
    assert acc_cand == pytest.approx(acc_cur, abs=0)
