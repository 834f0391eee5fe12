"""Set-up of one configuration: the trained model bank and θ.

The bank is built through the program's own offline steps, as the
paper's pre-processing set-up does and as ``chip_smoke.build_system``
does on the chip:

  * the detector (``train_models.train_detector``) at θ's detector
    resolution, its confidence chosen from the menu by F1 on the
    training clips;
  * the proxy, fitted on the detector's labels, its threshold by
    ``proxy.calibrate_threshold``;
  * the recurrent tracker, trained on the training clips' SORT tracks
    (``tracker.build_examples`` + ``tracker.train_tracker``), as the
    tuner's set-up does;
  * the window-size set (``windows.select_window_sizes``) under the area
    time model, also used for the planner's per-size times.

Training draws only on the configuration's fixed ``train_seed`` and the
profile's ``train`` clips, never on ``--seed``.  The trained arrays and
the chosen values go to ``bench/.cache/models/<config>.<profile>.npz``
(and ``.json``): the first run of a cell in a checkout trains, later
runs load.  The plain reference reads the same file, never the
program's objects.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

from bench.lib.registry import BENCH_DIR, ROOT, find_family

CACHE = os.path.join(BENCH_DIR, ".cache", "models")


@dataclass
class System:
    bank: object                 # repro.core.pipeline.ModelBank
    params: object               # repro.core.pipeline.PipelineParams (θ)
    weights: Dict[str, np.ndarray]   # flat "model/scope/leaf" arrays
    meta: dict                   # conf, threshold, sizes, training notes
    trained: bool                # True when this run trained


def flatten(tree, prefix: str) -> Dict[str, np.ndarray]:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}/{k}"))
        else:
            out[f"{prefix}/{k}"] = np.asarray(v, np.float32)
    return out


def unflatten(flat: Dict[str, np.ndarray], prefix: str):
    import jax.numpy as jnp
    tree: dict = {}
    for k, v in flat.items():
        if not k.startswith(prefix + "/"):
            continue
        parts = k[len(prefix) + 1:].split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(v)
    return tree


def pipeline_config(config: dict, root: str = ROOT):
    """The program's pipeline configuration the file names: a name in
    ``repro.configs.multiscope`` (``MULTISCOPE_PIPELINE``), with a
    ``.reduced`` suffix its ``.reduced()`` CPU size for tests; checked
    against the file: every width the file states must be the one the
    program runs (the detector's through its family's
    ``program_widths``)."""
    from repro.configs import multiscope
    name = config["pipeline"]
    cfg = getattr(multiscope, name.removesuffix(".reduced"))
    if name.endswith(".reduced"):
        cfg = cfg.reduced()
    det, prox, trk, win = (config["detector"], config["proxy"],
                           config["tracker"], config["windows"])
    checks = {
        "frame_size": (list(cfg.frame_size), config["frame_size"]),
        "proxy.cell": (cfg.proxy.cell, prox["cell"]),
        "proxy.base_channels": (cfg.proxy.base_channels,
                                prox["base_channels"]),
        "tracker.embed_dim": (cfg.tracker.embed_dim, trk["embed_dim"]),
        "tracker.rnn_dim": (cfg.tracker.rnn_dim, trk["rnn_dim"]),
        "tracker.match_hidden": (cfg.tracker.match_hidden,
                                 trk["match_hidden"]),
        "tracker.crop": (cfg.tracker.crop, trk["crop"]),
        "tracker.match_threshold": (cfg.tracker.match_threshold,
                                    trk["match_threshold"]),
        "tracker.max_tracks": (cfg.tracker.max_tracks, trk["max_tracks"]),
        "windows.k": (cfg.windows.k, win["k"]),
        "windows.max_windows": (cfg.windows.max_windows,
                                win["max_windows"]),
        "detector.max_dets": (cfg.detector.max_dets, det["max_dets"]),
    }
    checks.update(find_family(det["family"], root).program
                  .program_widths(det))
    theta = config["theta"]
    checks["theta.det_res in menu"] = (
        True, tuple(theta["det_res"]) in cfg.detector.resolutions)
    checks["theta.proxy_res in menu"] = (
        True, tuple(theta["proxy_res"]) in cfg.proxy.resolutions)
    checks["theta.gap in menu"] = (True, theta["gap"] in cfg.tracker.gaps)
    bad = {k: v for k, v in checks.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"configuration {config['name']} differs from "
                         f"the program's {config['pipeline']}: {bad}")
    return cfg


def cache_paths(config_name: str, profile: str) -> Tuple[str, str]:
    base = os.path.join(CACHE, f"{config_name}.{profile}")
    return base + ".npz", base + ".json"


def build(config: dict, profile: str, log=lambda *a: None,
          root: str = ROOT) -> System:
    """Load the configuration's bank for ``profile`` from the cache, or
    train it there."""
    cfg = pipeline_config(config, root)
    npz, meta_path = cache_paths(config["name"], profile)
    trained = False
    if os.path.exists(npz) and os.path.exists(meta_path):
        with np.load(npz) as z:
            weights = {k: z[k] for k in z.files}
        with open(meta_path) as f:
            meta = json.load(f)
    else:
        weights, meta = train(config, cfg, profile, log)
        os.makedirs(CACHE, exist_ok=True)
        tmp = npz + ".tmp.npz"
        np.savez(tmp, **weights)
        os.replace(tmp, npz)
        with open(meta_path + ".tmp", "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(meta_path + ".tmp", meta_path)
        trained = True
    bank, params = assemble(config, cfg, weights, meta)
    return System(bank, params, weights, meta, trained)


def assemble(config: dict, cfg, weights: Dict[str, np.ndarray],
             meta: dict):
    """The program's ``ModelBank`` and θ from cached arrays."""
    from repro.core import pipeline as pl
    from repro.core.detector import Detector
    from repro.core.proxy import ProxyModel
    from repro.core.windows import detector_time_model
    theta = config["theta"]
    arch = config["detector"]["arch"]
    det_res = tuple(theta["det_res"])
    proxy_res = tuple(theta["proxy_res"])
    det = Detector(arch, unflatten(weights, "detector"))
    bank = pl.ModelBank(cfg, {arch: det})
    bank.proxies = {proxy_res: ProxyModel(
        cfg.proxy.cell, cfg.proxy.base_channels, proxy_res,
        params=unflatten(weights, "proxy"))}
    bank.tracker_params = unflatten(weights, "tracker")
    grid = pl.det_grid(det_res)
    bank.sizes_cells = [tuple(s) for s in meta["sizes_cells"]]
    bank.ref_grid = grid
    area = detector_time_model(
        grid, 1.0, overhead_frac=config["train"]["time_model_overhead"])
    bank.win_times.update({(arch, s): area(s) for s in bank.sizes_cells})
    params = pl.PipelineParams(
        arch, det_res, float(meta["det_conf"]), gap=int(theta["gap"]),
        proxy_res=proxy_res, proxy_threshold=float(meta["proxy_threshold"]),
        tracker="recurrent", refine=False)
    return bank, params


def train(config: dict, cfg, profile: str, log) -> Tuple[dict, dict]:
    import jax.numpy as jnp
    from repro.core import pipeline as pl
    from repro.core.proxy import (ProxyModel, calibrate_threshold,
                                  cells_from_detections, proxy_loss)
    from repro.core.tracker import build_examples, train_tracker
    from repro.core.train_models import _fit, detector_f1, train_detector
    from repro.core.windows import (detector_time_model,
                                    select_window_sizes)
    from repro.data.video_synth import make_split

    tr, theta = config["train"], config["theta"]
    seed = int(tr["train_seed"])
    t0 = time.perf_counter()
    clips = make_split(profile, "train", int(tr["clips"]),
                       n_frames=int(tr["clip_frames"]))
    det_res = tuple(theta["det_res"])
    arch = config["detector"]["arch"]
    det, losses = train_detector(arch, clips, [det_res],
                                 steps=int(tr["det_steps"]),
                                 batch=int(tr["det_batch"]), seed=seed)
    t_det = time.perf_counter() - t0
    f1 = {c: detector_f1(det, clips, det_res, c, n_frames=16)
          for c in cfg.detector.confidences}
    conf = max(f1, key=f1.get)
    W, H = det_res
    fids = [(c, f) for c in clips for f in range(0, c.n_frames, 2)]
    frames = np.stack([pl.render_frame(c, f, W, H)[0] for c, f in fids])
    dets = det.detect_batch_bucketed(frames, conf)

    proxy_res = tuple(theta["proxy_res"])
    proxy = ProxyModel(cfg.proxy.cell, cfg.proxy.base_channels, proxy_res,
                       seed=seed)
    hc, wc = proxy.grid_shape()
    small = pl.downsample_chunk(frames, proxy_res)
    labels = np.stack([cells_from_detections(d, hc, wc) for d in dets])
    held = np.arange(len(frames)) % 4 == 0
    fr, lb = small[~held], labels[~held]
    rng = np.random.default_rng(seed)

    def batches():
        for _ in range(int(tr["proxy_steps"])):
            idx = rng.integers(len(fr), size=8)
            yield (jnp.asarray(fr[idx]), jnp.asarray(lb[idx]))

    proxy.params, _ = _fit(
        lambda p, f_, l_: proxy_loss(p, f_, l_, cfg.proxy.cell),
        proxy.params, batches(), lr=3e-3)
    scores, _ = proxy.scores_batch(small[held], 0.5)
    threshold = calibrate_threshold(list(scores), list(labels[held]),
                                    cfg.proxy.thresholds,
                                    min_recall=float(tr["proxy_min_recall"]))

    # window sizes: the greedy set selection over the detector's
    # labels, under the area time model (the tuner's step 5)
    grid = pl.det_grid(det_res)
    area = detector_time_model(grid, 1.0,
                               overhead_frac=tr["time_model_overhead"])
    grids = [cells_from_detections(d, grid[1], grid[0])
             for d in dets if len(d)]
    t1 = time.perf_counter()
    sizes = select_window_sizes(grids[:int(tr["size_grids"])], grid,
                                cfg.windows.k, area,
                                max_windows=cfg.windows.max_windows)
    t_sizes = time.perf_counter() - t1

    # recurrent tracker on the training clips' SORT tracks (the tuner's
    # step 6: the learned tracker does not exist yet, SORT labels it)
    bank = pl.ModelBank(cfg, {arch: det})
    bank.win_times[(arch, grid)] = area(grid)
    sort_theta = pl.PipelineParams(arch, det_res, conf, gap=1,
                                   proxy_res=None, tracker="sort",
                                   refine=False)
    examples = []
    for clip in clips:
        res = pl.run_clip(bank, sort_theta, clip)

        def get(f, clip=clip):
            return pl.render_frame(clip, f, W, H)[0]
        examples.extend(build_examples(res.tracks, get, cfg.tracker.crop,
                                       clip_key=clip.clip_id))
    t2 = time.perf_counter()
    tparams, tlosses = train_tracker(cfg.tracker, examples,
                                     steps=int(tr["tracker_steps"]),
                                     seed=seed)
    t_trk = time.perf_counter() - t2

    weights = {}
    weights.update(flatten(det.params, "detector"))
    weights.update(flatten(proxy.params, "proxy"))
    weights.update(flatten(tparams, "tracker"))
    meta = {
        "config": config["name"], "profile": profile,
        "det_conf": float(conf), "proxy_threshold": float(threshold),
        "sizes_cells": [list(map(int, s)) for s in sizes],
        "ref_grid": list(grid),
        "f1_by_conf": {str(k): float(v) for k, v in f1.items()},
        "det_loss_last": float(np.mean(losses[-10:])),
        "dets_per_frame": float(np.mean([len(d) for d in dets])),
        "label_positive_frac": float(labels.mean()),
        "tracker_examples": len(examples),
        "tracker_loss_last": float(np.mean(tlosses[-50:]))
        if tlosses else None,
        "seconds": {"detector": t_det, "sizes": t_sizes,
                    "tracker": t_trk,
                    "total": time.perf_counter() - t0},
    }
    log(f"trained {config['name']}.{profile}: {meta}")
    return weights, meta
