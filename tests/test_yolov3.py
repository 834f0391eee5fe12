"""YOLOv3 (Darknet-53) through the program's normal path, against the
benchmark's plain reference (``bench/reference/detectors/yolov3.py``),
at a reduced Darknet (``yolov3-r16``: the same topology, every width
divided by 16, one residual block a stage) on seeded random weights.

Tolerances: the program runs on batch norm folded into the conv weights
in float32, the reference applies it explicitly at
``Precision.HIGHEST``; on the CPU both are float32 convolutions summed in
another order, a few 1e-6 of the largest output after 39 convs.  The
raw outputs are compared to 2e-5 of their largest magnitude: several
times that noise, and below what either planted fault moves (darknet's
padding against XLA's 'SAME' moves whole rows; batch norm folded with
epsilon 1e-3 instead of 1e-5 moves a layer by up to 1e-3 of its
output).
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import models, registry  # noqa: E402
from bench.reference import detect as ref_detect  # noqa: E402
from bench.reference import nets  # noqa: E402
from repro.core import detector as dm  # noqa: E402
from repro.core import yolov3  # noqa: E402

ARCH = "yolov3-r16"
SPEC = yolov3.SPECS[ARCH]
RTOL = 2e-5
FAMILY = registry.find_family("yolov3")
FULL = registry.load_json(os.path.join(registry.BENCH_DIR, "configs",
                                       "ms-yolov3.json"))["detector"]


def det_block(arch: str = ARCH) -> dict:
    """The configuration's detector block at ``arch``'s widths."""
    s = yolov3.SPECS[arch]
    return dict(FULL, arch=arch, stem=s.stem, stages=list(s.stages),
                repeats=list(s.repeats), heads=list(s.heads))


def seeded(seed: int = 0):
    """Program parameters with seeded weights and batch-norm statistics
    near those of one batch of random frames, gamma and beta drawn at
    random."""
    raw = dm.init_detector(ARCH, seed)
    rng = np.random.default_rng(seed + 1)
    _, stats = yolov3._train_outputs(
        raw, jnp.asarray(frames(64, 64, n=4, seed=seed + 2)), ARCH)
    for name, *_, bn in yolov3.layers(SPEC):
        p = raw[name]
        c = p["w"].shape[-1]
        if bn:
            mean, var = (np.asarray(a) for a in stats[name])
            p["gamma"] = jnp.asarray(rng.uniform(0.5, 1.5, c), jnp.float32)
            p["beta"] = jnp.asarray(rng.normal(0, 0.1, c), jnp.float32)
            p["mean"] = jnp.asarray(mean + rng.normal(0, 0.05, c) *
                                    np.sqrt(var), jnp.float32)
            p["var"] = jnp.asarray(var * rng.uniform(0.8, 1.25, c),
                                   jnp.float32)
        else:
            # output convs scaled so logits and box regressions are a few
            # units, as a trained head's are
            p["w"] = p["w"] * 0.05
            p["b"] = jnp.asarray(rng.normal(0, 0.5, c), jnp.float32)
    return raw


@pytest.fixture(scope="module")
def params():
    return seeded(0)


def frames(h: int, w: int, n: int = 2, seed: int = 4) -> np.ndarray:
    return np.random.default_rng(seed).uniform(
        0, 1, (n, h, w, 3)).astype(np.float32)


def reference(raw, x):
    p = nets.take(models.flatten(raw, "detector"), "detector")
    return [np.asarray(a) for a in
            FAMILY.reference.forward(p, jnp.asarray(x), det_block())]


def outputs_match(raw, x, folded=None) -> bool:
    """The program's raw heads (batch norm folded) against the
    reference's (explicit), every scale, to ``RTOL`` of the largest."""
    folded = dm.fold_params(raw, ARCH) if folded is None else folded
    prog = [np.asarray(o) for o in
            yolov3.raw_outputs(folded, jnp.asarray(x), ARCH)]
    ref = reference(raw, x)
    for lvl, o in enumerate(prog):
        obj, cls, box = ref[3 * lvl:3 * lvl + 3]
        for want, got in ((obj, o[..., 4]),
                          (cls, o[..., 5 + SPEC.class_index]),
                          (box, o[..., :4])):
            if want.shape != got.shape:
                return False
            scale = float(np.abs(want).max())
            if float(np.abs(want - got).max()) > RTOL * scale:
                return False
    return True


# -- the forward --------------------------------------------------------------

@pytest.mark.parametrize("hw", [(64, 96), (32, 32)])
def test_each_scale_matches_the_reference(params, hw):
    assert outputs_match(params, frames(*hw))


def test_a_window_is_letterboxed_on_both_sides(params):
    """A 48x80 window (16-px multiples) runs as 64x96 padded bottom and
    right with 0.5, in the program and in the reference; cells whose
    origin lies on the padding score 0."""
    x = frames(48, 80)
    pad = np.full((2, 64, 96, 3), 0.5, np.float32)
    pad[:, :48, :80] = x
    folded = dm.fold_params(params, ARCH)
    a = yolov3.raw_outputs(folded, jnp.asarray(x), ARCH)
    b = yolov3.raw_outputs(folded, jnp.asarray(pad), ARCH)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    for u, v in zip(reference(params, x)[:-1], reference(params, pad)[:-1]):
        np.testing.assert_array_equal(u, v)
    assert outputs_match(params, x)
    sc, _ = dm._detect_scores(folded, jnp.asarray(x), ARCH)
    sc = np.asarray(sc)
    inside = yolov3.inside(48, 80, SPEC)
    assert sc.shape[1] == inside.size and not inside.all()
    assert (sc[:, ~inside] == 0).all() and (sc[:, inside] > 0).any()


@pytest.mark.parametrize("fault", ["same_padding", "wrong_bn_eps"])
def test_planted_faults_fail_the_comparison(params, monkeypatch, fault):
    x = frames(64, 96)
    assert outputs_match(params, x)
    folded = None
    if fault == "same_padding":
        def same(w, x, stride):
            return jax.lax.conv_general_dilated(
                x, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        monkeypatch.setattr(yolov3, "_conv", same)
    else:
        import dataclasses
        monkeypatch.setitem(yolov3.SPECS, ARCH,
                            dataclasses.replace(SPEC, bn_eps=1e-3))
        folded = yolov3.fold(params, ARCH)
    assert not outputs_match(params, x, folded)


def test_fold_equals_batch_norm_at_inference(params):
    """Folding is the inference form of the training forward's batch
    norm: with running statistics in place of the batch's, one conv
    gives the same numbers either way."""
    p = params["s1_r0_a"]
    x = jnp.asarray(frames(8, 8, seed=5)[..., :1].repeat(8, -1))
    y = yolov3._conv(p["w"], x, 1)
    explicit = (y - p["mean"]) / jnp.sqrt(p["var"] + SPEC.bn_eps) \
        * p["gamma"] + p["beta"]
    f = yolov3.fold(params, ARCH)["s1_r0_a"]
    folded = yolov3._conv(f["w"], x, 1) + f["b"]
    np.testing.assert_allclose(np.asarray(folded), np.asarray(explicit),
                               rtol=1e-5, atol=1e-5)


# -- decode --------------------------------------------------------------------

def test_decoded_boxes_match_the_reference_candidates(params):
    """``Detector.detect_batch`` on a window placed by origin and scale
    against the reference's ``candidates`` through its NMS: the same
    boxes (to 1e-4 of the frame) with the same scores."""
    det = dm.Detector(ARCH, params)
    x = frames(48, 80, n=1, seed=9)
    W, H = 160, 128
    origin, scale = (32 / W, 16 / H), (80 / W, 48 / H)
    sc, _ = dm._detect_scores(det.forward_params, jnp.asarray(x), ARCH)
    conf = float(np.quantile(np.asarray(sc)[np.asarray(sc) > 0], 0.9))
    got = det.detect_batch(x, conf, origins=[origin], scales=[scale],
                           max_dets=8)[0]
    ref = ref_detect.FrameDetections(conf, 0.45, 8)
    out = reference(params, x)
    cand = FAMILY.reference.candidates(tuple(o[0] for o in out), ref.lo,
                                       origin, scale, det_block())
    ref.add_window(cand)
    want = ref.finish(merge=False)
    assert len(want) > 1 and len(got) == len(want)
    got = got[np.argsort(-got[:, 4])]
    np.testing.assert_allclose(got[:, :4], want[:, :4], atol=1e-4)
    np.testing.assert_allclose(got[:, 4], want[:, 4], rtol=1e-4)
    assert (got[:, 0] >= origin[0]).all() and \
        (got[:, 0] <= origin[0] + scale[0] + 0.2).all()


def test_anchor_table_follows_the_flattened_outputs():
    tab = yolov3.anchor_table(64, 96, ARCH)
    # heads in the order stride 32, 16, 8; (row, column, anchor) inside
    assert tab.shape == ((2 * 3 + 4 * 6 + 8 * 12) * 3, 6)
    assert list(tab[0]) == [0, 0, 32, 116, 90, 0]
    assert list(tab[3]) == [1, 0, 32, 116, 90, 0]
    assert list(tab[18]) == [0, 0, 16, 30, 61, 1]
    assert list(tab[-1]) == [11, 7, 8, 33, 23, 2]


# -- the operation count ---------------------------------------------------------

@pytest.mark.parametrize("hw,share", [((544, 960), 0.99),
                                      ((272, 480), 0.99),
                                      ((48, 80), 0.975)])
def test_work_against_xla_cost_analysis(hw, share):
    """``work`` counts the 75 convs' taps inside the letterboxed input
    at the published widths; XLA's count of the reference adds batch
    norm (applied explicitly there), activations, biases, the letterbox
    and, at each of the five strided convs, one output row and column
    it computes and crops (its padding): 0.59% at 960x544, 0.74% at
    480x272 and 2.1% at a 48x80 window, where those rows are a larger
    share.  Never above, within ``share``."""
    det = det_block("yolov3")
    p = nets.take(models.flatten(dm.init_detector("yolov3", 0),
                                 "detector"), "detector")
    x = jax.ShapeDtypeStruct((1,) + hw + (3,), jnp.float32)
    ca = jax.jit(lambda p, a: FAMILY.reference.forward(p, a, det)) \
        .lower(p, x).compile().cost_analysis()
    xla = (ca[0] if isinstance(ca, list) else ca)["flops"]
    mine = FAMILY.program.work(det, *hw)[0]
    assert share * xla <= mine <= xla


def test_work_counts_the_published_size():
    """At 960x544 (30x17 cells of 32, no letterbox): 75 convs, 61.9 M
    parameters, about 194 GFLOP of taps inside the input (darknet's own
    count, which keeps the padding taps, is 65.9 at 416x416: 199 here)."""
    ops, byt = FAMILY.program.work(FULL, 544, 960)
    assert len(FAMILY.program.layer_list(FULL)) == 75
    assert 1.9e11 < ops < 1.99e11
    anchors = (17 * 30 + 34 * 60 + 68 * 120) * 3
    assert anchors == 32130
    assert byt > 4 * (544 * 960 * 3 + anchors * 5)


# -- training ----------------------------------------------------------------------

def test_targets_take_the_anchor_of_best_shape():
    """A 20x12 px box goes to the anchor of best shape IoU, (10, 13) at
    stride 8, in the cell holding its centre; a 120x90 box to (116, 90)
    at stride 32."""
    H, W = 128, 192
    boxes = [np.asarray([[40 / W, 20 / H, 20 / W, 12 / H, 0],
                         [50 / W, 40 / H, 120 / W, 90 / H, 1]])]
    obj, tbox, wt, gt, gm = yolov3.make_targets(boxes, H, W, ARCH)
    tab = yolov3.anchor_table(H, W, ARCH)
    pos = np.nonzero(obj[0])[0]
    assert len(pos) == 2 and gm[0].sum() == 2
    small = [k for k in pos if tab[k, 2] == 8][0]
    assert tuple(tab[small, 3:5]) == (10, 13)
    assert tuple(tab[small, :2]) == (40 // 8, 20 // 8)
    np.testing.assert_allclose(tbox[0, small, :2], [0.0, 0.5], atol=1e-6)
    big = [k for k in pos if tab[k, 2] == 32][0]
    assert tuple(tab[big, 3:5]) == (116, 90)
    np.testing.assert_allclose(tbox[0, big, 2:],
                               np.log([120 / 116, 90 / 90]), atol=1e-6)
    assert 1.0 < wt[0, big] < wt[0, small] < 2.0


def test_training_lowers_the_loss_and_keeps_running_statistics():
    from repro.data.video_synth import make_split
    from repro.core.pipeline import render_frame
    clips = make_split("caldot1", "train", 1, n_frames=8)
    raw = dm.init_detector(ARCH, 0)
    rng = np.random.default_rng(0)

    def sample(step):
        fs = [int(f) for f in rng.integers(8, size=4)]
        return (np.stack([render_frame(clips[0], f, 96, 64)[0]
                          for f in fs]),
                [clips[0].boxes_at(f) for f in fs])
    params, losses = yolov3.train(ARCH, raw, sample, 30)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < 0.7 * np.mean(losses[:5])
    assert not np.allclose(np.asarray(params["stem"]["mean"]), 0.0)
    assert not np.allclose(np.asarray(params["s2_r0_b"]["var"]), 1.0)
    det = dm.Detector(ARCH, params)
    assert det.forward_params["stem"].keys() == {"w", "b"}


# -- the ssd names the benchmark imports ---------------------------------------------

def test_the_ssd_names_keep_their_values():
    import inspect
    assert dm.STRIDE == 16
    assert dm.ARCHS == {"ssd-lite": ((12, 24, 48, 96), (0, 0, 0, 0)),
                        "ssd-deep": ((16, 32, 64, 128), (1, 1, 1, 1))}
    assert list(inspect.signature(dm.init_detector).parameters) == \
        ["arch", "seed"]
    assert list(inspect.signature(dm._detect_scores).parameters) == \
        ["params", "frames", "arch"]
    assert list(inspect.signature(dm.Detector).parameters)[:2] == \
        ["arch", "params"]
    from repro.core.train_models import train_detector
    assert list(inspect.signature(train_detector).parameters)[:6] == \
        ["arch", "clips", "resolutions", "steps", "batch", "seed"]
    p = dm.init_detector("ssd-deep", 0)
    assert dm.fold_params(p, "ssd-deep") is p
    s, b = dm._detect_scores(p, jnp.zeros((1, 64, 96, 3)), "ssd-deep")
    assert s.shape == (1, 4, 6) and b.shape == (1, 4, 6, 4)
    assert dm.family_name("ssd-lite") == "ssd"
    assert dm.family_name("yolov3") == "yolov3"
    with pytest.raises(KeyError):
        dm.family("yolov9")


def test_a_family_enters_by_its_table_entry_alone(monkeypatch):
    """A third family registered in ``FAMILIES`` runs through
    ``init_detector``, ``Detector.detect_batch`` (decode and span
    counters) and ``train_detector`` with no other edit."""
    from repro.core.train_models import train_detector
    from repro.data.video_synth import make_split
    from repro.obs.trace import TRACER

    def def_params(pb, arch):
        with pb.scope("head"):
            pb.param("w", (1, 1, 3, 1), (None, None, None, None))

    def scores(p, x, arch):
        w = p["head"]["w"][0, 0, :, 0]
        s = jax.nn.sigmoid(jnp.einsum("bhwc,c->bhw", x, w))
        return s.reshape(s.shape[0], -1), jnp.zeros(s.shape + (4,))

    def decode(s, b, conf, hw, arch, origin, scale, max_dets):
        return np.asarray([[origin[0], origin[1], 0.1, 0.1, s.max()]],
                          np.float32)

    fam = dm.Family({"toy": None}, def_params, lambda p, arch: p, scores,
                    decode, lambda s, conf, hw, arch: {"toy": len(s)},
                    lambda arch, p, sample, steps, lr: (
                        p, [float(len(sample(i)[1])) for i in range(steps)]))
    monkeypatch.setitem(dm.FAMILIES, "toy", fam)
    det = dm.Detector("toy", dm.init_detector("toy", 0))
    TRACER.clear()
    TRACER.enable(capacity=1 << 8)
    try:
        out = det.detect_batch(frames(16, 32, n=2), 0.0,
                               origins=[(0.0, 0.0), (0.5, 0.25)],
                               scales=[(0.5, 0.5)] * 2)
        args = {s.name: s.args for s in TRACER.snapshot()}
    finally:
        TRACER.disable()
    assert [float(d[0, 0]) for d in out] == [0.0, 0.5]
    assert args["detect.decode"]["toy"] == 2
    assert args["detect.decode"]["candidates"] == 2 * 16 * 32
    clips = make_split("caldot1", "train", 1, n_frames=2)
    trained, losses = train_detector("toy", clips, [(32, 16)], steps=2,
                                     batch=3)
    assert trained.arch == "toy" and losses == [3.0, 3.0]


def test_ssd_detect_batch_is_unchanged():
    """ssd's scores, boxes and decoded detections equal the parent's
    recipe: ``_detect_scores`` then ``decode_detections`` per window."""
    p = dm.init_detector("ssd-lite", 0)
    det = dm.Detector("ssd-lite", p)
    x = frames(64, 96, n=3, seed=2)
    s, b = (np.asarray(a) for a in dm._detect_scores(p, jnp.asarray(x),
                                                     "ssd-lite"))
    conf = float(np.quantile(s, 0.9))
    got = det.detect_batch(x, conf)
    for i in range(3):
        want = dm.decode_detections(s[i], b[i], conf)
        np.testing.assert_array_equal(got[i], want)


# -- spans ---------------------------------------------------------------------------

def test_traced_detect_batch_emits_the_new_span_args(params):
    from repro.obs.trace import TRACER
    det = dm.Detector(ARCH, params)
    x = frames(48, 80, n=2, seed=9)
    sc, _ = dm._detect_scores(det.forward_params, jnp.asarray(x), ARCH)
    conf = float(np.quantile(np.asarray(sc)[np.asarray(sc) > 0], 0.95))
    TRACER.clear()
    TRACER.enable(capacity=1 << 10)
    try:
        det.detect_batch(x, conf)
        spans = TRACER.snapshot()
    finally:
        TRACER.disable()
    by = {s.name: s for s in spans}
    n = int(sc.shape[1])
    # scores (2, n) and raw boxes (2, n, 4), float32
    assert by["detect.wait"].args["d2h_bytes"] == 2 * n * 4 * 5
    dec = by["detect.decode"].args
    over = int((np.asarray(sc) > conf).sum())
    assert dec["candidates"] == over > 0
    assert dec["cand_s8"] + dec["cand_s16"] + dec["cand_s32"] == over
    assert dec["padded"] == 2
    assert by["detect.upload"].args["h2d_bytes"] == x.nbytes


def test_large_batches_run_in_device_slices(params):
    det = dm.Detector(ARCH, params)
    x = frames(32, 32, n=32, seed=3)
    whole = [np.asarray(a) for a in det._scores(jnp.asarray(x))]
    parts = [np.asarray(a) for a in dm._detect_scores(
        det.forward_params, jnp.asarray(x[16:]), ARCH)]
    np.testing.assert_array_equal(whole[0][16:], parts[0])
    np.testing.assert_array_equal(whole[1][16:], parts[1])


# -- the normal path -------------------------------------------------------------------

def test_run_clips_on_the_reduced_yolov3_pipeline():
    """``ModelBank`` -> ``executor.run_clips`` -> DETECT with the
    reduced YOLOv3 pipeline: full frames and sub-frame windows (16-px
    multiples, letterboxed) both run, and the tuner's per-size timing
    prices the architecture."""
    from repro.configs.multiscope import YOLOV3_PIPELINE
    from repro.core import pipeline as pl
    from repro.core.executor import run_clips
    from repro.core.proxy import ProxyModel
    from repro.core.tracker import init_tracker
    from repro.data.video_synth import make_split
    cfg = YOLOV3_PIPELINE.reduced()
    assert cfg.detector.archs == (ARCH,)
    clips = make_split("caldot1", "test", 2, n_frames=8)
    det = dm.Detector(ARCH, seeded(1))
    bank = pl.ModelBank(cfg, {ARCH: det})
    res = cfg.proxy.resolutions[-1]
    proxy = ProxyModel(cfg.proxy.cell, cfg.proxy.base_channels, res)
    bank.proxies = {res: proxy}
    W, H = cfg.detector.resolutions[-1]
    grid = pl.det_grid((W, H))
    bank.sizes_cells = [grid, (3, 2), (5, 3)]
    bank.ref_grid = grid
    bank.tracker_params = init_tracker(cfg.tracker)
    frame, _ = pl.render_frame(clips[0], 0, W, H)
    s, _ = proxy.scores(pl._downsample(frame, res))
    sc, _ = dm._detect_scores(det.forward_params, jnp.asarray(frame[None]),
                              ARCH)
    conf = float(np.quantile(np.asarray(sc), 0.99))
    params = pl.PipelineParams(ARCH, (W, H), conf, gap=1, proxy_res=res,
                               proxy_threshold=float(np.quantile(s, 0.85)),
                               tracker="recurrent", refine=False)
    results, _ = run_clips(bank, params, clips)
    assert len(results) == 2
    assert sum(r.frames_processed for r in results) == 16
    assert any(r.detector_windows > r.full_frames for r in results)
    assert all(len(t) for r in results for t in r.tracks)
    assert pl.measure_window_time(bank, ARCH, (3, 2)) > 0
