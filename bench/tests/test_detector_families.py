"""CPU tests of the detector-family seam: a family's reference, decode
and operation count are found by name, and a family enters by files
alone."""
from __future__ import annotations

import functools
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testkit as kit

from bench.lib import registry

ACCURATE = registry.load_json(os.path.join(registry.BENCH_DIR, "configs",
                                           "ms-accurate.json"))
DETECTORS = {"ssd-lite": kit.TINY_CONFIG["detector"],
             "ssd-deep": ACCURATE["detector"]}


# -- the oracle: the ssd reference and decode as they stood before the seam --

@functools.partial(jax.jit, static_argnames=("channels", "extra_convs",
                                             "operands"))
def oracle_detector(p, frames, channels, extra_convs, operands=None):
    from bench.reference.nets import conv
    x = frames
    for i in range(len(channels)):
        x = jax.nn.relu(conv(x, p[f"block{i}_down/w"], p[f"block{i}_down/b"],
                             2, operands))
        for j in range(extra_convs[i]):
            x = jax.nn.relu(conv(x, p[f"block{i}_conv{j}/w"],
                                 p[f"block{i}_conv{j}/b"], 1, operands))
    out = conv(x, p["head/w"], p["head/b"], 1, operands)
    return out[..., 0], out[..., 1:]


def oracle_decode(logits, boxes, lo, origin, scale):
    hc, wc = logits.shape
    ii, jj = np.nonzero(logits > lo)
    lg = logits[ii, jj].astype(np.float64)
    bx = boxes[ii, jj].astype(np.float64)
    cx = origin[0] + (jj + np.clip(bx[:, 0], 0, 1)) / wc * scale[0]
    cy = origin[1] + (ii + np.clip(bx[:, 1], 0, 1)) / hc * scale[1]
    w = np.exp(np.clip(bx[:, 2], -5, 5)) / wc * scale[0]
    h = np.exp(np.clip(bx[:, 3], -5, 5)) / hc * scale[1]
    return np.stack([cx, cy, w, h, lg], axis=1).reshape(-1, 5)


def seeded_weights(det, seed):
    """He-scaled normal weights for an ssd detector block."""
    rng = np.random.default_rng(seed)
    p, cin = {}, 3

    def conv(name, k, ci, co):
        p[f"{name}/w"] = rng.normal(0, np.sqrt(2 / (k * k * ci)),
                                    (k, k, ci, co)).astype(np.float32)
        p[f"{name}/b"] = rng.normal(0, 0.1, (co,)).astype(np.float32)
    for i, (c, e) in enumerate(zip(det["channels"], det["extra_convs"])):
        conv(f"block{i}_down", 3, cin, c)
        for j in range(e):
            conv(f"block{i}_conv{j}", 3, c, c)
        cin = c
    conv("head", 1, cin, 5)
    return {k: jnp.asarray(v) for k, v in p.items()}


@pytest.mark.parametrize("operands", [None, "float8_e4m3fn"])
@pytest.mark.parametrize("hw", [(160, 256), (48, 64)])
@pytest.mark.parametrize("arch", sorted(DETECTORS))
def test_ssd_family_matches_the_oracle_bit_for_bit(arch, hw, operands):
    det = DETECTORS[arch]
    fam = registry.find_family("ssd").reference
    p = seeded_weights(det, 3)
    frames = jnp.asarray(np.random.default_rng(4).uniform(
        0, 1, (2,) + hw + (3,)).astype(np.float32))
    got = fam.forward(p, frames, det, operands=operands)
    want = oracle_detector(p, frames, channels=tuple(det["channels"]),
                           extra_convs=tuple(det["extra_convs"]),
                           operands=operands)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    logits, boxes = (np.asarray(a) for a in want)
    lo = float(np.quantile(logits, 0.7))
    for i in range(len(frames)):
        origin, scale = (0.25, 0.125 * i), (0.5, 0.4)
        cand = fam.candidates((logits[i], boxes[i]), lo, origin, scale, det)
        ref = oracle_decode(logits[i], boxes[i], lo, origin, scale)
        assert len(ref) > 0
        assert cand.dtype == ref.dtype and np.array_equal(cand, ref)


def test_operation_counts_equal_the_parents():
    """``ms-accurate`` at 960x544 with its smallest window, on made-up
    counters: the floats the counts gave before the seam."""
    from bench.lib import flops
    sizes = [(60, 34), (7, 9), (11, 10)]
    counters = {"full_frames": 1234, "detector_windows": 1500,
                "frames_processed": 1300}
    theta = ACCURATE["theta"]
    assert theta["det_res"] == [960, 544]
    assert flops.detector_work(ACCURATE, theta, sizes, counters) == (
        4199093367936.0, 9600482856)
    assert flops.proxy_work(ACCURATE, theta, counters) == (
        91524056000.0, 2173813200)


# -- a family that enters by files alone --------------------------------------

def _root_with_family(tmp, family, files=("reference", "lib")):
    """A tiny root whose configuration names ``family``, made from the
    ``ssd`` family's files copied under that name."""
    root = kit.make_root(tmp)
    for side in files:
        d = os.path.join(root, "bench", side, "detectors")
        shutil.copy(os.path.join(d, "ssd.py"), os.path.join(d, f"{family}.py"))
    path = os.path.join(root, "bench", "configs", "tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["detector"]["family"] = family
    kit.write(root, "bench/configs/tiny.json", cfg)
    return root


def _check(root, seed):
    cell = registry.find_cell("tiny.batch", root=root)
    entry = registry.find_entry(cell.entry, root=root)
    st = entry.setup(cell, seed, 1.0, lambda *a: None)
    entry.window(st)
    return entry.check(st)["program"]


def test_a_family_enters_by_files_alone(tmp_path, monkeypatch):
    from bench.lib import models
    cache = str(tmp_path / "models")
    kit.tiny_models(cache, "caldot1")
    monkeypatch.setattr(models, "CACHE", cache)
    ssd = _check(kit.make_root(str(tmp_path / "ssd")), 23)
    copy = _check(_root_with_family(str(tmp_path / "copy"), "ssdcopy"), 23)
    assert ssd["det_gaps"]["n"] > 0
    assert copy == ssd

    with pytest.raises(FileNotFoundError):
        registry.find_family("nosuch")
    half = _root_with_family(str(tmp_path / "half"), "halfssd",
                             files=("reference",))
    with pytest.raises(FileNotFoundError):
        _check(half, 23)
