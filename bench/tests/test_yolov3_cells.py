"""CPU tests of the ``yolov3`` detector family in the benchmark: a tiny
cell with the reduced Darknet (``yolov3-r16``) runs through
``run_cell`` and the plain reference, the control and a planted fault
fail it, and ``d2h_mb_per_frame`` reads the pulls."""
from __future__ import annotations

import os

import numpy as np
import pytest

import bench_testkit as kit

from bench.lib import registry

CELL = "tinyyolo.batch"
ARCH = "yolov3-r16"


def _config() -> dict:
    from repro.core.yolov3 import SPECS
    s = SPECS[ARCH]
    full = registry.load_json(os.path.join(
        registry.BENCH_DIR, "configs", "ms-yolov3.json"))["detector"]
    det = dict(full, arch=ARCH, stem=s.stem, stages=list(s.stages),
               repeats=list(s.repeats), heads=list(s.heads), max_dets=24,
               routes=[16, 12])     # darknet's layer indices, one block a stage
    from repro.core.yolov3 import layers
    det["parameters"] = sum(k * k * ci * co + (2 * co if b else co)
                            for _, k, _, ci, co, b in layers(s))
    det["convs"] = len(layers(s))
    return dict(kit.TINY_CONFIG, name="tinyyolo", detector=det,
                pipeline="YOLOV3_PIPELINE.reduced")


def _models(cache: str, config: dict) -> None:
    """Seeded weights (batch-norm statistics from one batch, the output
    convs scaled so logits are a few units) in the model cache, the
    confidence a score quantile."""
    import jax.numpy as jnp
    from bench.lib import models
    from repro.configs.multiscope import YOLOV3_PIPELINE
    from repro.core import detector as dm
    from repro.core import yolov3
    from repro.core.proxy import init_proxy
    from repro.core.tracker import init_tracker
    from repro.data.video_synth import make_clip
    kit.tiny_models(cache, "caldot1")     # the proxy threshold, sizes
    cfg = YOLOV3_PIPELINE.reduced()
    W, H = config["theta"]["det_res"]
    clip = make_clip("caldot1", "train", 0, 4)
    batch = np.stack([clip.render(f, W, H) for f in range(4)])
    raw = dm.init_detector(ARCH, 0)
    _, stats = yolov3._train_outputs(raw, jnp.asarray(batch), ARCH)
    for name, *_, bn in yolov3.layers(yolov3.SPECS[ARCH]):
        if bn:
            raw[name]["mean"], raw[name]["var"] = stats[name]
        else:
            raw[name]["w"] = raw[name]["w"] * 0.05
    det = dm.Detector(ARCH, raw)
    s, _ = dm._detect_scores(det.forward_params, jnp.asarray(batch[:1]),
                             ARCH)
    conf = float(np.quantile(np.asarray(s), 0.997))
    import json
    with open(os.path.join(cache, "tiny.caldot1.json")) as f:
        meta = json.load(f)
    meta["det_conf"] = conf
    # few positive cells, so the planner cuts sub-frame windows
    from repro.core.proxy import proxy_scores
    pw, ph = config["theta"]["proxy_res"]
    ps, _ = proxy_scores(init_proxy(cfg.proxy.cell, cfg.proxy.base_channels,
                                    0),
                         jnp.asarray(clip.render(0, pw, ph)[None]),
                         cfg.proxy.cell, 0.5)
    meta["proxy_threshold"] = float(np.quantile(np.asarray(ps), 0.95))
    weights = {}
    weights.update(models.flatten(raw, "detector"))
    weights.update(models.flatten(init_proxy(cfg.proxy.cell,
                                             cfg.proxy.base_channels, 0),
                                  "proxy"))
    weights.update(models.flatten(init_tracker(cfg.tracker, 0), "tracker"))
    np.savez(os.path.join(cache, "tinyyolo.caldot1.npz"), **weights)
    with open(os.path.join(cache, "tinyyolo.caldot1.json"), "w") as f:
        json.dump(meta, f)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tinyyolo"))
    root = kit.make_root(tmp, cells=((CELL, "tiny.batch", kit.TINY_BATCH),))
    config = _config()
    kit.write(root, "bench/configs/tinyyolo.json", config)
    bench = registry.load_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "tinyyolo", "source": "CPU test",
                             "file": "bench/configs/tinyyolo.json",
                             "reduced": [], "why": "CPU test size"})
    for w in bench["workloads"]:
        if w["name"] == CELL:
            w["config"] = "tinyyolo"
    kit.write(root, "BENCHMARK.json", bench)
    cache = os.path.join(tmp, "models")
    _models(cache, config)
    return root, cache


def _setup(tiny, monkeypatch, seed):
    from bench.lib import models
    root, cache = tiny
    monkeypatch.setattr(models, "CACHE", cache)
    cell = registry.find_cell(CELL, root=root)
    entry = registry.find_entry("batch", root=root)
    st = entry.setup(cell, seed, 1.0, lambda *a: None)
    entry.window(st)
    return cell, entry, st


def test_tiny_yolov3_cell_runs_correct_and_fails_the_control(tiny,
                                                               monkeypatch):
    from bench.reference.compare import verdict
    cell, entry, st = _setup(tiny, monkeypatch, 5)
    assert cell.config["detector"]["family"] == "yolov3"
    w = st.window
    assert w["detector_windows"] > w["full_frames"] > 0, w
    prec = cell.config["precision"]
    got = entry.check(st, control={
        "conv_operands": prec["control_conv_operands"],
        "host_operands": prec["control_host_operands"]})
    assert got["program"]["det_gaps"]["n"] > 0
    ok, rows = verdict(got["program"], kit.LIMITS)
    assert ok, rows
    ok, rows = verdict(got["control"], kit.LIMITS)
    assert not ok, rows


def test_a_wrong_letterbox_fill_makes_correct_false(tiny, monkeypatch):
    """Windows padded with 0 instead of darknet's 0.5 grey: the
    sub-frame windows' detections move and the check fails."""
    import jax.numpy as jnp
    from bench.reference.compare import verdict
    from repro.core import yolov3
    from repro.core.detector import _detect_scores

    def black(frames, spec):
        B, H, W, C = frames.shape
        hp, wp = yolov3.letterboxed(H, W, spec.multiple)
        return jnp.pad(frames, ((0, 0), (0, hp - H), (0, wp - W), (0, 0)))
    monkeypatch.setattr(yolov3, "letterbox", black)
    # compiled forwards of the unpatched program must not be reused
    _detect_scores.clear_cache()
    try:
        cell, entry, st = _setup(tiny, monkeypatch, 5)
        assert st.window["detector_windows"] > st.window["full_frames"]
        ok, rows = verdict(entry.check(st)["program"], kit.LIMITS)
    finally:
        _detect_scores.clear_cache()
    assert not ok, rows


class _Span:
    def __init__(self, name, args):
        self.name, self.args = name, args


class _Ctx:
    def __init__(self, spans, frames=10):
        self.spans = spans
        self.counters = {"frames_processed": frames}


def test_d2h_reads_the_pulled_bytes_and_nothing_without_them():
    m = registry.find_metric("d2h_mb_per_frame.batch")
    spans = [_Span("detect.wait", {"d2h_bytes": 3_000_000}),
             _Span("detect.wait", {"d2h_bytes": 2_000_000}),
             _Span("detect.upload", {"h2d_bytes": 9})]
    assert m.read(_Ctx(spans)) == pytest.approx(0.5)
    # a program whose spans carry no d2h_bytes (one built before them)
    assert m.read(_Ctx([_Span("detect.wait", None)])) is None
    assert m.read(_Ctx(spans, frames=0)) is None


def test_the_new_cells_resolve_with_their_parts():
    for name, family in (("yolov3.caldot1", "yolov3"),
                         ("accurate.jackson", "ssd")):
        cell = registry.find_cell(name)
        assert cell.config["detector"]["family"] == family
        names = {m["name"] for m in cell.per_layer}
        assert {"d2h_mb_per_frame.batch", "detect_roofline.batch",
                "pipeline_mfu.batch", "device_idle.batch"} <= names
        assert {m["name"] for m in cell.end_to_end} == {"frames_per_s",
                                                        "setup_s"}
    assert registry.find_cell("accurate.caldot1").per_layer[-1]["name"] \
        == "d2h_mb_per_frame.batch"


def test_the_yolov3_configuration_matches_the_program():
    """Every width ``ms-yolov3.json`` states is the program's, and the
    published size holds: 75 convs, 61,949,149 parameters."""
    from bench.lib import models
    cfg = registry.load_json(os.path.join(registry.BENCH_DIR, "configs",
                                          "ms-yolov3.json"))
    pipe = models.pipeline_config(cfg)
    assert pipe.detector.archs == ("yolov3",)
    assert cfg["reduced"] == []
    assert cfg["detector"]["parameters"] == 61949149
    assert cfg["detector"]["convs"] == 75
    acc = registry.load_json(os.path.join(registry.BENCH_DIR, "configs",
                                          "ms-accurate.json"))
    for block in ("theta", "tracker", "proxy", "windows", "train"):
        assert cfg[block] == acc[block], block
    models.pipeline_config(acc)


@pytest.mark.parametrize("key,value", [("routes", [62, 36]),
                                       ("cell_px", 32),
                                       ("stages", [64, 128, 256, 512, 512]),
                                       ("bn_eps", 1e-3)])
def test_a_value_the_program_does_not_run_is_refused(key, value):
    from bench.lib import models
    cfg = registry.load_json(os.path.join(registry.BENCH_DIR, "configs",
                                          "ms-yolov3.json"))
    cfg["detector"][key] = value
    with pytest.raises(ValueError, match=f"detector.{key}"):
        models.pipeline_config(cfg)
