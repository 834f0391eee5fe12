"""The readers of the spans inside the executor's stages, on a span list
built by hand (no pipeline runs)."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

import bench_testkit as kit  # noqa: F401  (puts the repo on sys.path)

from bench.lib import registry
from repro.obs.trace import Span

MS = 1_000_000
FRAMES = 4


def _span(sid, parent, name, ts, dur, args=None):
    return Span(sid, parent, name, "", ts, dur, 0, 1, "cam", 0, args)


def _ctx(spans, frames=FRAMES):
    return SimpleNamespace(spans=spans,
                           counters={"frames_processed": frames})


def _chunk():
    """One chunk: a run, its four stages and their children (times in
    ms).  A 2 ms ``detect.decode`` inside ``detect.upload`` checks that
    self time leaves children out; a ``detect.wait`` still open counts
    nothing."""
    rows = [
        (1, None, "run", 0, 100, None),
        (2, 1, "stage.decode", 0, 10, {"h2d_bytes": 3_000_000}),
        (3, 1, "stage.proxy", 10, 10, None),
        (4, 3, "proxy.downsample", 10, 2, None),
        (5, 3, "proxy.wait", 12, 5, {"h2d_bytes": 1_000_000}),
        (6, 1, "stage.detect", 20, 40, None),
        (7, 6, "detect.upload", 20, 12, {"h2d_bytes": 4_000_000}),
        (8, 7, "detect.decode", 24, 2, {"windows": 1, "dets": 0}),
        (9, 6, "detect.wait", 32, 8, None),
        (10, 6, "detect.decode", 40, 16, {"windows": 4, "dets": 9}),
        (11, 1, "stage.track", 60, 40, None),
        (12, 11, "track.crops", 60, 4, {"crops": 9}),
        (13, 11, "track.wait", 64, 6, {"h2d_bytes": 2_000_000}),
        (14, 11, "track.assoc", 70, 28, {"frames": FRAMES}),
        (15, 14, "detect.wait", 80, -1, None),        # still open
    ]
    return [_span(sid, p, n, ts * MS, dur * MS if dur > 0 else dur, a)
            for sid, p, n, ts, dur, a in rows]


EXPECTED = {
    "host_ms_per_frame.proxy_downsample.batch": 2 / FRAMES,
    "host_ms_per_frame.detect_upload.batch": (12 - 2) / FRAMES,
    "host_ms_per_frame.detect_decode.batch": (2 + 16) / FRAMES,
    "host_ms_per_frame.track_crops.batch": 4 / FRAMES,
    "host_ms_per_frame.track_assoc.batch": 28 / FRAMES,
    "device_wait_ms_per_frame.batch": (5 + 8 + 6) / FRAMES,
    "h2d_mb_per_frame.batch": (3 + 1 + 4 + 2) / FRAMES,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_built_chunk(name):
    got = registry.find_metric(name).read(_ctx(_chunk()))
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_child_spans_reads_nothing(name):
    """A program with no child spans (only ``run`` and ``stage.*``), or
    a window with no frame, reads ``None``: the line leaves it out."""
    stages_only = [s for s in _chunk()
                   if s.name == "run" or s.name.startswith("stage.")]
    metric = registry.find_metric(name)
    assert metric.read(_ctx(stages_only)) is None
    assert metric.read(_ctx(_chunk(), frames=0)) is None


def test_kind_absent_from_the_window_reads_zero():
    """A window whose chunks cut no crops and sent nothing reads 0.0
    for those kinds, not ``None``."""
    spans = [s for s in _chunk() if s.name not in ("track.crops",)]
    for s in spans:
        s.args = None if s.args and "h2d_bytes" in s.args else s.args
    ctx = _ctx(spans)
    assert registry.find_metric(
        "host_ms_per_frame.track_crops.batch").read(ctx) == 0.0
    assert registry.find_metric("h2d_mb_per_frame.batch").read(ctx) == 0.0


def test_every_reader_is_in_the_benchmark():
    bench = registry.benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = per_layer[name]
        assert m["source"] == "program_span"
        assert m["moves"] == "frames_per_s" and m["better"] == "lower"
        assert m["workloads"] == ["accurate.caldot1"]


def test_span_breakdown_tool_on_a_hand_built_chunk():
    """``bench/tools/span_breakdown.py``: each stage's summed duration,
    the share its children cover, and the totals the stage metrics and
    the readers above split it into."""
    from bench.tools.span_breakdown import breakdown
    got = breakdown(_chunk(), FRAMES)
    detect = got["stages"]["stage.detect"]
    assert detect["ms_per_frame"] == pytest.approx(40 / FRAMES)
    assert detect["covered"] == pytest.approx(36 / 40)
    assert detect["children"] == pytest.approx(
        {"detect.upload": 12 / FRAMES, "detect.wait": 8 / FRAMES,
         "detect.decode": 16 / FRAMES})
    assert got["stages"]["stage.track"]["covered"] == pytest.approx(
        38 / 40)
    assert got["spans"]["detect.decode"] == pytest.approx(
        {"n": 2, "ms_per_frame": 18 / FRAMES,
         "self_ms_per_frame": 18 / FRAMES})
    assert got["spans"]["stage.proxy"]["self_ms_per_frame"] == \
        pytest.approx(3 / FRAMES)
    assert "detect.wait" in got["spans"]     # the open one is left out
    assert got["spans"]["detect.wait"]["n"] == 1
