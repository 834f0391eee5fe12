"""A tiny cell for the benchmark's CPU tests.

The configuration runs the program's ``MULTISCOPE_PIPELINE.reduced()``
(256x160 frames, an 8 px proxy cell) with seeded untrained weights made
by the program's init functions, so no test trains: the detector's
confidence and the proxy's threshold are quantiles of their scores on a
training frame, which leaves a few detections and some positive cells a
frame.  Files are written into a copy of ``bench/`` under a temporary
root, as a later change would add them.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

TINY_CONFIG = {
    "name": "tiny",
    "source": "MULTISCOPE_PIPELINE.reduced() of src/repro/configs/multiscope.py",
    "pipeline": "MULTISCOPE_PIPELINE.reduced",
    "frame_size": [256, 160],
    "detector": {"family": "ssd", "arch": "ssd-lite",
                 "channels": [12, 24, 48, 96],
                 "extra_convs": [0, 0, 0, 0], "stride_px": 16,
                 "cell_px": 16, "max_dets": 24, "nms_iou": 0.45},
    "proxy": {"cell": 8, "base_channels": 4},
    "tracker": {"embed_dim": 16, "rnn_dim": 32, "match_hidden": 32,
                "crop": 8, "match_threshold": 0.2, "max_tracks": 32,
                "max_misses": 2, "min_hits": 2},
    "windows": {"k": 3, "max_windows": 4},
    "theta": {"det_res": [256, 160], "proxy_res": [64, 40], "gap": 1},
    "train": {"train_seed": 0, "time_model_overhead": 0.0},
    "precision": {"control_conv_operands": "float8_e4m3fn",
                  "control_host_operands": "bfloat16"},
    "assumed": [], "reduced": [],
}

TINY_BATCH = {"entry": "batch", "profile": "caldot1", "clip_frames": 12,
              "clip_ids": [0, 1]}
LIMITS = {"proxy_gap": 1e-3, "det_gap_p99": 1e-3, "box_gap_px": 1e-2,
          "track_gap": 1e-3, "rows_unexplained": 0.0}


def make_root(tmp: str, cells=(("tiny.batch", "tiny.batch", TINY_BATCH),)
              ) -> str:
    """A checkout-like root: ``BENCHMARK.json`` with the tiny cells and
    a copy of ``bench/`` holding their files."""
    root = os.path.join(tmp, "root")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "tests",
                                                  "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg_file = "bench/configs/tiny.json"
    bench["configs"].append({"name": "tiny", "source": TINY_CONFIG["source"],
                             "file": cfg_file, "reduced": [],
                             "why": "CPU test size"})
    write(root, cfg_file, TINY_CONFIG)
    for cell, traffic, mix in cells:
        write(root, f"bench/traffic/{traffic}.json", mix)
        write(root, f"bench/limits/{cell}.json", {"limits": LIMITS})
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": traffic, "chips": 1,
                                   "why": "CPU test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m and _entry_of(m, bench, root) == mix["entry"]:
                m["workloads"].append(cell)
    write(root, "BENCHMARK.json", bench)
    return root


def _entry_of(metric, bench, root) -> str:
    cells = {w["name"]: w for w in bench["workloads"]}
    for name in metric["workloads"]:
        if name in cells:
            path = os.path.join(root, "bench", "traffic",
                                cells[name]["traffic"] + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)["entry"]
    return ""


def write(root: str, rel: str, obj) -> None:
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_models(cache_dir: str, profile: str) -> None:
    """Seeded untrained weights for the tiny configuration, in the model
    cache, with the confidence and threshold set from score quantiles."""
    from bench.lib import models
    from repro.configs.multiscope import MULTISCOPE_PIPELINE
    from repro.core.detector import _detect_scores, init_detector
    from repro.core.proxy import init_proxy, proxy_scores
    from repro.core.tracker import init_tracker
    from repro.data.video_synth import make_clip
    import jax.numpy as jnp
    cfg = MULTISCOPE_PIPELINE.reduced()
    c = TINY_CONFIG
    det = init_detector(c["detector"]["arch"], 0)
    prox = init_proxy(cfg.proxy.cell, cfg.proxy.base_channels, 0)
    trk = init_tracker(cfg.tracker, 0)
    clip = make_clip(profile, "train", 0, 4)
    W, H = c["theta"]["det_res"]
    frame = clip.render(0, W, H)[None]
    s, _ = _detect_scores(det, jnp.asarray(frame), c["detector"]["arch"])
    conf = float(np.quantile(np.asarray(s), 0.985))
    pw, ph = c["theta"]["proxy_res"]
    small = clip.render(0, pw, ph)[None]
    ps, _ = proxy_scores(prox, jnp.asarray(small), cfg.proxy.cell, 0.5)
    thr = float(np.quantile(np.asarray(ps), 0.8))
    weights = {}
    weights.update(models.flatten(det, "detector"))
    weights.update(models.flatten(prox, "proxy"))
    weights.update(models.flatten(trk, "tracker"))
    meta = {"det_conf": conf, "proxy_threshold": thr,
            "sizes_cells": [[16, 10], [4, 3], [8, 5]], "ref_grid": [16, 10]}
    os.makedirs(cache_dir, exist_ok=True)
    np.savez(os.path.join(cache_dir, f"tiny.{profile}.npz"), **weights)
    with open(os.path.join(cache_dir, f"tiny.{profile}.json"), "w") as f:
        json.dump(meta, f)
