"""Observability layer (``repro.obs``): the no-perturbation contract —
tracing off records nothing and tracing on never changes any pipeline
output — plus the span exporters, the metrics registry, the shared
stage-timing assembly, the drift monitors and the pure latency-report
aggregation."""
import json
import threading

import numpy as np
import pytest

from repro.configs.multiscope import MULTISCOPE_PIPELINE
from repro.core import pipeline as pl
from repro.core.executor import (BatchBroker, ExecutorOptions,
                                 TrackBroker, run_clip_streamed)
from repro.core.proxy import ProxyModel
from repro.core.tracker import init_tracker
from repro.core.train_models import train_detector
from repro.data.video_synth import make_split
from repro.obs import metrics as om
from repro.obs.metrics import (REGISTRY, DriftMonitor, Registry,
                               RunProfile, assert_stage_sane,
                               disable_drift, empty_stage_block,
                               enable_drift, merge_stage_blocks,
                               stage_block)
from repro.obs.trace import TRACER


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test leaves the module-level tracer off and empty and the
    drift flag cleared — no cross-test leakage."""
    yield
    TRACER.disable()
    TRACER.clear()
    disable_drift()


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
    W, H = cfg.detector.resolutions[-1]
    frame, _ = pl.render_frame(clips[0], 0, W, H)
    s, _ = proxy.scores(pl._downsample(frame, res))
    return bank, clips, res, float(np.quantile(s, 0.85))


def _params(bank, res, th, **kw):
    base = dict(det_arch="ssd-lite",
                det_res=bank.cfg.detector.resolutions[-1],
                det_conf=0.4, gap=1, proxy_res=res, proxy_threshold=th,
                tracker="sort", refine=False)
    base.update(kw)
    return pl.PipelineParams(**base)


def _flavors(bank, params, clip):
    """The four executor flavors the bit-identity acceptance names.
    Each returns (tracks, dispatches) for one run of ``clip``."""

    def sequential():
        r = pl.run_clip_frames(bank, params, clip)
        return r.tracks, None

    def streaming():
        r = run_clip_streamed(bank, params, clip,
                              ExecutorOptions(prefetch=False))
        return r.tracks, r.dispatches

    def device_tracker():
        r = run_clip_streamed(
            bank, params, clip,
            ExecutorOptions(prefetch=False, device_tracker=True))
        return r.tracks, r.dispatches

    def track_broker():
        tb = TrackBroker()
        try:
            r = run_clip_streamed(
                bank, params, clip,
                ExecutorOptions(prefetch=False, device_assign=True,
                                track_broker=tb))
        finally:
            tb.close()
        return r.tracks, r.dispatches

    return {"sequential": sequential, "streaming": streaming,
            "device_tracker": device_tracker,
            "track_broker": track_broker}


# ---------------------------------------------------------------------------
# the no-perturbation contract
# ---------------------------------------------------------------------------

def test_disabled_tracer_records_nothing(exec_bank):
    """Tracing off (the default): a full streamed run leaves the ring
    buffer empty — the instrumentation sites never reach the tracer."""
    bank, clips, res, th = exec_bank
    TRACER.disable()
    TRACER.clear()
    run_clip_streamed(bank, _params(bank, res, th), clips[0],
                      ExecutorOptions(prefetch=False))
    assert TRACER.snapshot() == []
    assert TRACER.current() is None


def test_tracing_on_is_bit_identical_across_flavors(exec_bank):
    """The acceptance gate: for each executor flavor, tracks AND
    dispatch counts with tracing enabled equal the tracing-off run bit
    for bit — the tracer observes, never perturbs."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=8)
    for clip in clips:
        for name, flavor in _flavors(bank, params, clip).items():
            TRACER.disable()
            ref_tracks, ref_disp = flavor()
            TRACER.enable()
            TRACER.clear()
            got_tracks, got_disp = flavor()
            n_spans = len(TRACER.snapshot())
            TRACER.disable()
            assert got_disp == ref_disp, (name, got_disp, ref_disp)
            assert len(got_tracks) == len(ref_tracks), name
            for a, b in zip(ref_tracks, got_tracks):
                np.testing.assert_array_equal(a, b, err_msg=name)
            if name != "sequential":      # per-frame path is untraced
                assert n_spans > 0, f"{name}: tracing on emitted no spans"


def test_tracing_collects_run_and_stage_spans(exec_bank):
    """An enabled streamed run emits one ``run`` root and per-chunk
    ``stage.*`` children parented to it, all tagged with the stream."""
    bank, clips, res, th = exec_bank
    TRACER.enable()
    TRACER.clear()
    run_clip_streamed(bank, _params(bank, res, th, chunk_size=8),
                      clips[0], ExecutorOptions(prefetch=False))
    spans = TRACER.snapshot()
    TRACER.disable()
    roots = [s for s in spans if s.name == "run"]
    assert len(roots) == 1 and roots[0].dur >= 0
    stages = [s for s in spans if s.name.startswith("stage.")]
    assert {s.name for s in stages} >= {"stage.decode", "stage.proxy"}
    for s in stages:
        assert s.parent == roots[0].sid
        assert s.stream == roots[0].stream
        assert s.dur >= 0 and s.proc >= 0


CHILD_STAGE = {"proxy.downsample": "stage.proxy",
               "proxy.wait": "stage.proxy",
               "detect.upload": "stage.detect",
               "detect.wait": "stage.detect",
               "detect.decode": "stage.detect",
               "track.crops": "stage.track",
               "track.wait": "stage.track",
               "track.assoc": "stage.track"}


def _traced_recorded_run(bank, params, clip, options):
    """A traced run that also keeps each chunk's window plan and
    detections -> (spans, {chunk: plan}, {chunk: dets})."""
    from repro.core import executor as ex
    plans, dets = {}, {}

    def detect(ctx, task):
        out = ex.stage_detect(ctx, task)
        plans[task.index], dets[task.index] = out.plan, out.dets
        return out
    TRACER.enable()
    TRACER.clear()
    ex.ClipExecutor(bank, params, options,
                    stages={"detect": detect}).run(clip)
    spans = TRACER.snapshot()
    TRACER.disable()
    return spans, plans, dets


@pytest.mark.parametrize("double_buffer", [False, True])
def test_child_spans_nest_in_their_stage_and_count_uploads(
        exec_bank, double_buffer):
    """A traced run emits all eight child spans; each is a child of
    its stage's span of the same chunk and lies inside it, and every
    ``h2d_bytes`` is the ``nbytes`` of the padded host arrays the
    shapes give: proxy frames, full-frame detector batches, the chunk
    upload (in DECODE when double-buffered, else lazily in DETECT) with
    each window table, and the crop batch."""
    from repro.core.detector import next_bucket
    from repro.core.pipeline import CELL_PX
    bank, clips, res, th = exec_bank
    B = 8
    params = _params(bank, res, th, chunk_size=B, tracker="recurrent")
    spans, plans, dets = _traced_recorded_run(
        bank, params, clips[0],
        ExecutorOptions(prefetch=False, double_buffer=double_buffer))
    by_sid = {s.sid: s for s in spans}
    assert {s.name for s in spans} >= set(CHILD_STAGE)
    for s in spans:
        if s.name not in CHILD_STAGE:
            continue
        p = by_sid[s.parent]
        assert p.name == CHILD_STAGE[s.name], s.name
        assert s.chunk == p.chunk and s.stream == p.stream
        assert p.ts <= s.ts and s.ts + s.dur <= p.ts + p.dur, s.name

    W, H = params.det_res
    frame = H * W * 3 * 4
    pw, ph = res
    crop = bank.cfg.tracker.crop

    def h2d(name, chunk):
        return sum(s.args.get("h2d_bytes", 0) for s in spans
                   if s.name == name and s.chunk == chunk and s.args)
    for k, plan in plans.items():
        n = len(plan.windows)
        assert h2d("proxy.wait", k) == next_bucket(n) * ph * pw * 3 * 4
        decoded = h2d("stage.decode", k)
        assert decoded in (0, B * frame)
        assert decoded == 0 or double_buffer
        want, lazy = 0, decoded == 0
        for size, entries in plan.by_size.items():
            if (size[0] * CELL_PX, size[1] * CELL_PX) == (W, H):
                want += next_bucket(len(entries)) * frame
            else:
                want += next_bucket(len(entries)) * 3 * 4
                if lazy:
                    want, lazy = want + B * frame, False
        assert h2d("detect.upload", k) == want, k
        total = sum(len(d) for d in dets[k])
        wait = next_bucket(total, min_bucket=max(8, B // 2)) * crop \
            * crop * 3 * 4 if total else 0
        assert h2d("track.wait", k) == wait, k


@pytest.mark.parametrize("threshold", ["calibrated", "skip_all"])
def test_track_assoc_carries_host_twin_counters(exec_bank, threshold):
    """Each traced chunk's ``track.assoc`` span carries the recurrent
    tracker's ``jv_steps`` and ``fma_ties`` for that chunk: together
    they are the run's whole host-twin work, and a chunk with no
    detections reads 0 ``jv_steps``."""
    from repro.core import fastmath as fm
    from repro.core import hungarian as hg
    bank, clips, res, th = exec_bank
    th = th if threshold == "calibrated" else float("inf")
    params = _params(bank, res, th, chunk_size=8, tracker="recurrent")
    jv0, ties0 = hg.COUNTS.jv_steps, fm.COUNTS.fma_ties
    spans, plans, dets = _traced_recorded_run(
        bank, params, clips[0], ExecutorOptions(prefetch=False))
    assoc = {s.chunk: s for s in spans if s.name == "track.assoc"}
    assert set(assoc) == set(dets)
    for k, s in assoc.items():
        assert set(s.args) == {"frames", "jv_steps", "fma_ties"}
        if not any(len(d) for d in dets[k]):
            assert s.args["jv_steps"] == 0
    assert sum(s.args["jv_steps"] for s in assoc.values()) \
        == hg.COUNTS.jv_steps - jv0
    assert sum(s.args["fma_ties"] for s in assoc.values()) \
        == fm.COUNTS.fma_ties - ties0
    n_dets = sum(len(d) for ds in dets.values() for d in ds)
    if threshold == "skip_all":
        assert n_dets == 0
    else:
        assert n_dets > 0
        assert sum(s.args["jv_steps"] for s in assoc.values()) > 0


def test_disabled_sites_never_reach_the_tracer(exec_bank, monkeypatch):
    """Tracing off: no site opens, emits or annotates a span (so none
    allocates a context manager, takes a tracer timestamp or a lock),
    on the paths the child spans instrument."""
    from repro.obs.trace import Tracer
    bank, clips, res, th = exec_bank

    def refuse(*a, **kw):
        raise AssertionError("a disabled site reached the tracer")
    for name in ("span", "open", "emit", "add", "_annotate"):
        monkeypatch.setattr(Tracer, name, refuse)
    TRACER.disable()
    for opts in (ExecutorOptions(prefetch=False, double_buffer=False),
                 ExecutorOptions()):
        run_clip_streamed(bank, _params(bank, res, th, chunk_size=8,
                                        tracker="recurrent"),
                          clips[0], opts)


def test_span_parent_inheritance_and_add():
    """``parent=`` overrides the thread's stack; a span without its
    own stream and chunk takes its parent's; ``add`` accumulates into
    the innermost open span only."""
    TRACER.enable()
    TRACER.clear()
    root = TRACER.open("run", "test", stream="cam1")
    with TRACER.span("stage.x", "test", stream="cam1", chunk=3,
                     parent=root.sid) as st:
        TRACER.add("h2d_bytes", 5)
        with TRACER.span("x.child", "test", args={"k": 1}) as ch:
            TRACER.add("h2d_bytes", 2)
        TRACER.add("h2d_bytes", 7)
    TRACER.close(root)
    TRACER.disable()
    assert st.parent == root.sid and ch.parent == st.sid
    assert (ch.stream, ch.chunk) == ("cam1", 3)
    assert st.args == {"h2d_bytes": 12}
    assert ch.args == {"k": 1, "h2d_bytes": 2}
    assert TRACER.current() is None


def test_spans_are_profiler_annotations_on_its_clock(exec_bank, tmp_path):
    """With tracing on, a JAX profile holds host events named as the
    spans (``stage.detect``, ``detect.decode``), one per span, and
    their starts agree with the tracer's after the offset taken at an
    anchor annotation, to within 1 ms."""
    import glob
    import time

    import jax
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    TRACER.enable()
    TRACER.clear()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.anchor"):
            t0 = time.perf_counter_ns()
            run_clip_streamed(bank, params, clips[0],
                              ExecutorOptions(prefetch=False))
    finally:
        jax.profiler.stop_trace()
        TRACER.disable()
    spans = TRACER.snapshot()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(ev.start_ns)
    anchor, = events["test.anchor"]
    offset = anchor - t0
    for name in ("stage.detect", "detect.decode"):
        got = sorted(events.get(name, []))
        want = sorted(s.ts + offset for s in spans if s.name == name)
        assert want and len(got) == len(want), name
        assert max(abs(g - w) for g, w in zip(got, want)) < 1_000_000


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_chrome_export_16_stream_broker_run(exec_bank, tmp_path):
    """16 concurrent per-frame streams through one BatchBroker export a
    valid Chrome trace: loads with ``json.load``, one pid lane per
    stream plus the shared broker lane, X events with monotone
    non-negative microsecond timestamps."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th, chunk_size=1)
    TRACER.enable()
    TRACER.clear()
    broker = BatchBroker()
    results = [None] * 16
    errors = []

    def one(i):
        try:
            results[i] = run_clip_streamed(
                bank, params, clips[i % len(clips)],
                ExecutorOptions(prefetch=False, batch_broker=broker))
        except BaseException as exc:
            errors.append(exc)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    broker.close()
    assert not errors, errors
    path = tmp_path / "trace.json"
    n = TRACER.export_chrome(str(path))
    TRACER.disable()
    with open(path) as f:
        events = json.load(f)          # round-trips as plain JSON
    xs = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert len(xs) == n > 0
    # one process lane per stream + the shared broker lane
    lanes = {m["args"]["name"] for m in metas}
    assert "(shared)" in lanes and len(lanes) == len(clips) + 1
    last = -1.0
    for e in xs:
        assert e["ts"] >= last >= -1.0     # sorted ascending
        assert e["dur"] >= 0.0
        last = e["ts"]
    assert any(e["name"] == "broker.detect.flush" for e in xs)
    assert any(e["name"] == "run" for e in xs)


def test_jsonl_export_roundtrip(tmp_path):
    """JSON-lines export: one parseable object per span, sorted by
    start time, parent links preserved."""
    TRACER.enable()
    TRACER.clear()
    with TRACER.span("outer", "test", stream="cam0") as so:
        with TRACER.span("inner", "test") as si:
            assert si.parent == so.sid
    TRACER.emit("follow", "test", ts=si.ts + si.dur + 1, dur=5)
    path = tmp_path / "spans.jsonl"
    n = TRACER.export_jsonl(str(path))
    TRACER.disable()
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == n == 3
    assert [ln["name"] for ln in lines] == ["outer", "inner", "follow"]
    by_name = {ln["name"]: ln for ln in lines}
    assert by_name["inner"]["parent"] == by_name["outer"]["sid"]
    assert by_name["outer"]["stream"] == "cam0"
    ts = [ln["ts_ns"] for ln in lines]
    assert ts == sorted(ts)
    assert all(ln["dur_ns"] >= 0 for ln in lines)


def test_ring_buffer_bounds_memory():
    tr = TRACER
    tr.enable(capacity=16)
    tr.clear()
    for i in range(100):
        tr.emit("e", ts=i, dur=1)
    spans = tr.snapshot()
    tr.disable()
    tr.enable(capacity=65536)        # restore the default capacity
    tr.disable()
    assert len(spans) == 16
    assert spans[0].ts == 84 and spans[-1].ts == 99


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_kinds_and_snapshot():
    reg = Registry()
    reg.counter("a.hits").inc(3)
    reg.gauge("a.depth").set(2.5)
    h = reg.histogram("b.lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["a.hits"] == 3 and snap["a.depth"] == 2.5
    assert snap["b.lat"]["count"] == 4
    assert snap["b.lat"]["mean"] == pytest.approx(2.5)
    assert snap["b.lat"]["min"] == 1.0 and snap["b.lat"]["max"] == 4.0
    assert snap["b.lat"]["p50"] == pytest.approx(2.5)
    # prefix filter
    assert set(reg.snapshot("a.")) == {"a.hits", "a.depth"}
    # a name keeps its kind
    with pytest.raises(TypeError):
        reg.gauge("a.hits")
    # the whole snapshot is JSON-serializable (benches embed it)
    json.dumps(snap)


def test_registry_reset_keeps_cached_references():
    """Instrumentation sites cache metric objects at construction;
    ``reset`` must zero IN PLACE so those references stay live."""
    reg = Registry()
    c = reg.counter("x.n")
    c.inc(7)
    reg.reset()
    assert c.value == 0
    c.inc()
    assert reg.snapshot()["x.n"] == 1
    assert reg.counter("x.n") is c


def test_registry_is_thread_safe():
    reg = Registry()
    c = reg.counter("t.n")
    h = reg.histogram("t.h")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000 and h.count == 8000


def test_global_registry_populated_by_pipeline(exec_bank):
    """A streamed run folds its stage timings and dispatch counts into
    the module-level REGISTRY under the documented names."""
    bank, clips, res, th = exec_bank
    REGISTRY.reset()
    r = run_clip_streamed(bank, _params(bank, res, th), clips[0],
                          ExecutorOptions(prefetch=False))
    snap = REGISTRY.snapshot()
    assert snap["executor.dispatch.proxy"] == r.dispatches["proxy"]
    assert snap["executor.dispatch.detect"] == r.dispatches["detect"]
    for st in r.stage_seconds:
        assert snap[f"executor.stage.{st}.wall_seconds"]["count"] >= 1
    assert snap["detector.dispatches"] >= r.dispatches["detect"]


# ---------------------------------------------------------------------------
# stage-timing assembly (the shared helper the benches use)
# ---------------------------------------------------------------------------

def test_stage_block_helpers():
    b = stage_block({"decode": 1.0, "proxy": 2.0}, {"decode": 0.5})
    assert b == {"decode": {"wall": 1.0, "process": 0.5},
                 "proxy": {"wall": 2.0, "process": 0.0}}
    assert empty_stage_block(["a"]) == {"a": {"wall": 0.0,
                                              "process": 0.0}}
    merged = merge_stage_blocks([b, None, b])
    assert merged["decode"] == {"wall": 2.0, "process": 1.0}
    assert merged["proxy"]["wall"] == 4.0
    assert_stage_sane(merged)
    assert_stage_sane(None)
    with pytest.raises(AssertionError):
        assert_stage_sane({"x": {"wall": 0.1, "process": 0.5}})


def test_run_profile_thread_safe_and_publishes():
    prof = RunProfile(["decode", "detect"])

    def work():
        for _ in range(500):
            prof.note_stage("decode", 0.001, 0.0005)
            prof.dispatch("detect")

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ss = prof.stage_seconds()
    assert ss["decode"]["wall"] == pytest.approx(2.0)
    assert ss["decode"]["process"] == pytest.approx(1.0)
    assert prof.dispatches("detect") == 2000
    assert_stage_sane(ss)
    reg = Registry()
    prof.publish(reg, prefix="executor")
    snap = reg.snapshot()
    assert snap["executor.dispatch.detect"] == 2000
    assert snap["executor.stage.decode.wall_seconds"]["count"] == 1


# ---------------------------------------------------------------------------
# drift monitors
# ---------------------------------------------------------------------------

def test_drift_monitor_flags_content_shift():
    mon = DriftMonitor(window=4, trailing=8)
    for w in range(12):                      # steady regime
        mon.observe(w, proxy_fracs=[0.2, 0.22], track_count=3)
    assert not mon.drifted()
    s = mon.summary()
    assert s["watermarks"] == 12 and s["last_watermark"] == 11
    assert s["proxy_score"]["delta"] == pytest.approx(0.0)
    assert sum(s["proxy_score"]["hist"]) == 12
    for w in range(12, 16):                  # content shift
        mon.observe(w, proxy_fracs=[0.8], track_count=9)
    assert mon.drifted()
    s = mon.summary()
    assert s["proxy_score"]["delta"] > 0.3
    assert s["track_count"]["delta"] > 2.0


def test_drift_collection_is_opt_in(exec_bank):
    """proxy_fracs ride on RunResult only while drift is enabled, and
    enabling it never changes the tracks."""
    bank, clips, res, th = exec_bank
    params = _params(bank, res, th)
    opts = ExecutorOptions(prefetch=False)
    r_off = run_clip_streamed(bank, params, clips[0], opts)
    assert r_off.proxy_fracs is None
    enable_drift()
    try:
        r_on = run_clip_streamed(bank, params, clips[0], opts)
    finally:
        disable_drift()
    assert r_on.proxy_fracs is not None
    assert len(r_on.proxy_fracs) == r_on.frames_processed
    assert all(0.0 <= f <= 1.0 for f in r_on.proxy_fracs)
    for a, b in zip(r_off.tracks, r_on.tracks):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# latency-report aggregation (pure function)
# ---------------------------------------------------------------------------

def test_summarize_latency_per_dataset_and_counters():
    from repro.query.service import QueryStats, summarize_latency

    assert summarize_latency([]) == {"queries": 0}
    hist = [
        QueryStats(scan_seconds=0.1, ingest_seconds=0.0,
                   skipped_clips=1, indexed_clips=1, scanned_clips=1,
                   n_clips=3, datasets="caldot1"),
        QueryStats(scan_seconds=0.3, ingest_seconds=0.2,
                   ingested_clips=2, scanned_clips=2, n_clips=2,
                   datasets="caldot1"),
        QueryStats(scan_seconds=0.2, indexed_clips=4, n_clips=4,
                   datasets="caldot1+shibuya"),
        QueryStats(scan_seconds=0.4, n_clips=0),      # no datasets
    ]
    rep = summarize_latency(hist)
    # flat keys bit-compatible with the pre-breakdown report
    assert rep["queries"] == 4
    assert rep["warm_queries"] == 3
    assert rep["scan_seconds_total"] == pytest.approx(1.0)
    assert rep["scan_seconds_median"] == pytest.approx(0.25)
    assert rep["ingest_seconds_total"] == pytest.approx(0.2)
    # clip-disposition totals (what plan.run always computed)
    assert rep["clips_skipped_total"] == 1
    assert rep["clips_indexed_total"] == 5
    assert rep["clips_scanned_total"] == 3
    assert rep["clips_total"] == 9
    # per-dataset breakdown groups on the "+"-joined touched sets
    ds = rep["datasets"]
    assert set(ds) == {"caldot1", "caldot1+shibuya", "(none)"}
    assert ds["caldot1"]["queries"] == 2
    assert ds["caldot1"]["warm_queries"] == 1
    assert ds["caldot1+shibuya"]["scan_seconds_median"] \
        == pytest.approx(0.2)
    assert ds["(none)"]["queries"] == 1
    json.dumps(rep)                 # benches embed it verbatim


def test_query_service_latency_report_live(exec_bank, tmp_path):
    """End to end: real queries against a warm store produce the
    per-dataset breakdown and clip counters."""
    from repro.query import Query, QueryService, TrackStore

    bank, clips, res, th = exec_bank
    store = TrackStore(str(tmp_path / "store"), bank,
                       _params(bank, res, th))
    service = QueryService(store)
    service.warm(clips)
    for _ in range(3):
        service.query(Query.count_frames(min_count=1), clips)
    rep = service.latency_report()
    assert rep["queries"] >= 3
    assert rep["clips_total"] >= 3 * len(clips)
    assert set(rep["datasets"]) == {"caldot1"}
    assert rep["datasets"]["caldot1"]["queries"] == rep["queries"]
