"""What the timed path produced, against the plain reference.

For every checked stream (a clip run) the comparison reads, frame by frame:

  proxy_gap   largest reference margin (score - threshold) of a
              detector cell the reference marks positive and the
              program's window plan leaves uncovered;
  det_gap_p99 the 99th percentile, over every detection of the run,
              of the detection decision gap in logits, the reference
              (the configuration's detector family: its ``forward``
              and ``candidates``, ``bench/reference/detectors``) run on
              the program's own windows (``detect.frame_gaps``):
              a program detection's gap to its reference candidate, or
              a reference detection's margin where the program lacks
              it.  (The largest gap, ``det_gap_max``, is reported beside
              it and not compared: a widest gap of some thousands rests
              on one near-tie, and on the chip it did not separate
              sound runs from the control by three times.);
  box_gap_px  largest coordinate difference between a program
              detection and the reference's nearest candidate box, in
              pixels at the detector's resolution;
  track_gap   largest tracker decision gap in probability units, the
              reference replayed on the program's own history
              (``track.replay``);
  rows_unexplained  output track rows that are no detection of their
              frame or continue an ended track (exact: limit 0).

With ``control`` the same numbers are read for the control: the
reference computed with lower-precision operands, its own outputs put
in the program's place (its positive cells as the plan, its detections
on the program's windows, its own assignments on the program's
history).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from bench.lib.registry import ROOT, find_family
from bench.reference import detect, nets, plan, track

NUMBERS = ("proxy_gap", "det_gap_p99", "box_gap_px", "track_gap",
           "rows_unexplained")
DET_QUANTILE = 0.99
QUANTILES = (0.5, 0.9, 0.95, 0.99, 0.995, 0.999, 1.0)
BATCH = 16


@dataclass
class Stream:
    clip: object
    frame_ids: List[int]
    windows: List[list]          # per frame, the program's plan
    dets: List[np.ndarray]       # per frame, the program's detections
    tracks: List[np.ndarray]     # the program's output tracks


def _sizes_order(wins):
    order: Dict[tuple, list] = {}
    for w in wins:
        order.setdefault(tuple(w[2]), []).append(w)
    return [w for ws in order.values() for w in ws]


def check_stream(s: Stream, weights: Dict[str, np.ndarray], config: dict,
                 theta: dict, frame_fn: Callable,
                 control: Optional[dict] = None,
                 root: str = ROOT) -> Dict[str, float]:
    det_cfg, trk_cfg = config["detector"], config["tracker"]
    family = find_family(det_cfg["family"], root).reference
    W, H = theta["det_res"]
    cell_px = int(det_cfg["cell_px"])
    grid = (W // cell_px, H // cell_px)
    conf, thr = float(theta["det_conf"]), float(theta["proxy_threshold"])
    nms_iou, max_dets = float(det_cfg["nms_iou"]), int(det_cfg["max_dets"])
    levels = int(np.log2(config["proxy"]["cell"]))
    pd, pp = nets.take(weights, "detector"), nets.take(weights, "proxy")
    pt = nets.take(weights, "tracker")
    cop = None if control is None else control["conv_operands"]
    hop = None if control is None else control["host_operands"]
    frames = np.stack([frame_fn(s.clip, f) for f in s.frame_ids]) \
        if s.frame_ids else np.zeros((0, H, W, 3), np.float32)
    out = {k: 0.0 for k in NUMBERS}
    cout = {k: 0.0 for k in NUMBERS}
    gaps = {"program": [], "control": [], "program_vs_control": []}
    worst = {"program": {"gap": -1.0}, "control": {"gap": -1.0},
             "program_vs_control": {"gap": -1.0}}
    pvc_box = 0.0

    def note(side, k, g, w):
        gaps[side].extend(g)
        if w["gap"] > worst[side]["gap"]:
            worst[side] = dict(w, frame=int(s.frame_ids[k]),
                               windows=[[int(x), int(y), [int(a) for a in z]]
                                        for x, y, z in s.windows[k]])

    # -- proxy: coverage of the reference's positive cells --------------
    small = plan.downsample(frames, tuple(theta["proxy_res"]))
    prox = functools.partial(nets.proxy, pp, levels=levels)
    sref = nets.batched(prox, small, BATCH)
    sctl = nets.batched(functools.partial(prox, operands=cop), small,
                        BATCH) if control else None
    for k in range(len(frames)):
        m = plan.cell_margins(sref[k], thr, grid)
        out["proxy_gap"] = max(out["proxy_gap"], plan.plan_gap(
            m, plan.covered(s.windows[k], grid)))
        if control:
            mc = plan.cell_margins(sctl[k], thr, grid)
            cout["proxy_gap"] = max(cout["proxy_gap"],
                                    plan.plan_gap(m, mc > 0))

    # -- detection on the program's windows -----------------------------
    by_size: Dict[tuple, list] = {}
    for k, wins in enumerate(s.windows):
        for w in _sizes_order(wins):
            by_size.setdefault(tuple(w[2]), []).append((k, w))
    results: Dict[tuple, dict] = {}
    for size, items in by_size.items():
        pw, ph = size[0] * cell_px, size[1] * cell_px
        crops = np.stack([frames[k, y * cell_px:y * cell_px + ph,
                                 x * cell_px:x * cell_px + pw]
                          for k, (x, y, _) in items])
        fn = functools.partial(family.forward, pd, det_cfg=det_cfg)
        ref_out = nets.batched(fn, crops, BATCH)
        if control:
            ctl_out = nets.batched(functools.partial(fn, operands=cop),
                                   crops, BATCH)
        for i, (k, (x, y, _)) in enumerate(items):
            origin = (x * cell_px / W, y * cell_px / H)
            results[(k, x, y, size)] = dict(
                ref=tuple(o[i] for o in ref_out), origin=origin,
                scale=(pw / W, ph / H),
                ctl=tuple(o[i] for o in ctl_out) if control else None)
    for k, wins in enumerate(s.windows):
        full = len(wins) == 1 and tuple(wins[0][2]) == grid
        ref = detect.FrameDetections(conf, nms_iou, max_dets)
        ctl = detect.FrameDetections(conf, nms_iou, max_dets)
        for x, y, size in _sizes_order(wins):
            r = results[(k, x, y, tuple(size))]
            ref.add_window(family.candidates(r["ref"], ref.lo, r["origin"],
                                             r["scale"], det_cfg))
            if control:
                ctl.add_window(family.candidates(
                    r["ctl"], ctl.lo, r["origin"], r["scale"], det_cfg))
        ref.finish(merge=not full)
        g, b, w = detect.frame_gaps(s.dets[k], ref, nms_iou, W, H)
        note("program", k, g, w)
        out["box_gap_px"] = max(out["box_gap_px"], b)
        if control:
            g, b, w = detect.frame_gaps(ctl.finish(merge=not full), ref,
                                        nms_iou, W, H)
            note("control", k, g, w)
            cout["box_gap_px"] = max(cout["box_gap_px"], b)
            # the program against the control's own decisions: where the
            # control is the program's precision, a second witness
            g, b, w = detect.frame_gaps(s.dets[k], ctl, nms_iou, W, H)
            note("program_vs_control", k, g, w)
            pvc_box = max(pvc_box, b)

    # -- tracking on the program's history ------------------------------
    C = int(trk_cfg["crop"])
    allc = [track.crops(frames[k], np.asarray(d).reshape(-1, 5), C)
            for k, d in enumerate(s.dets)]
    counts = [len(c) for c in allc]
    flat = np.concatenate(allc) if allc else np.zeros((0, C, C, 3))
    cnn = functools.partial(nets.crop_cnn, pt)
    xr = nets.batched(cnn, flat.astype(np.float32), 64)
    xr = np.zeros((0, trk_cfg["embed_dim"])) if xr is None else xr
    split = np.cumsum(counts)[:-1]
    xs = [np.asarray(a, np.float64) for a in np.split(xr, split)]
    ctl_arg = None
    if control:
        xc = nets.batched(functools.partial(cnn, operands=cop),
                          flat.astype(np.float32), 64)
        xc = np.zeros((0, trk_cfg["embed_dim"])) if xc is None else xc
        ctl_arg = (track.Heads(weights, hop),
                   [np.asarray(a, np.float64) for a in np.split(xc, split)])
    t = track.replay(s.frame_ids, s.dets, xs, s.tracks,
                     track.Heads(weights), trk_cfg, control=ctl_arg)
    out["track_gap"] = t["track_gap"]
    out["rows_unexplained"] = t["rows_unexplained"]
    res = {"program": dict(out, _gaps=gaps["program"],
                           _worst=worst["program"])}
    if control:
        cout["track_gap"] = t["control_track_gap"]
        res["control"] = dict(cout, _gaps=gaps["control"],
                              _worst=worst["control"])
        res["program_vs_control"] = {
            "box_gap_px": pvc_box, "_gaps": gaps["program_vs_control"],
            "_worst": worst["program_vs_control"]}
    return res


def gap_quantile(gaps: Sequence[float], q: float) -> float:
    """The ``q`` quantile of the gaps, a reading of one of them (0 for
    none)."""
    if not len(gaps):
        return 0.0
    return float(np.quantile(np.asarray(gaps, np.float64), q,
                             method="higher"))


def check(streams: Sequence[Stream], weights, config, theta, frame_fn,
          control: Optional[dict] = None,
          root: str = ROOT) -> Dict[str, Dict[str, float]]:
    """Each number over the streams: the largest reading, and the
    detection gap's quantile over every detection of every stream.
    Each side also carries ``det_gap_max``, ``det_gaps`` (count and
    ``QUANTILES``) and ``det_worst`` (the largest gap's frame, plan and
    decision), which no limit reads."""
    agg: Dict[str, dict] = {}
    gaps: Dict[str, list] = {}
    worst: Dict[str, dict] = {}
    for s in streams:
        res = check_stream(s, weights, config, theta, frame_fn, control,
                           root)
        for side, vals in res.items():
            a = agg.setdefault(side, {k: 0.0 for k in NUMBERS})
            gaps.setdefault(side, []).extend(vals.pop("_gaps"))
            w = vals.pop("_worst")
            if w["gap"] > worst.get(side, {"gap": -1.0})["gap"]:
                worst[side] = dict(w, clip=getattr(s.clip, "clip_id", None))
            for k, v in vals.items():
                a[k] = max(a[k], float(v))
    agg.setdefault("program", {k: 0.0 for k in NUMBERS})
    for side, a in agg.items():
        g = gaps.get(side, [])
        a["det_gap_p99"] = gap_quantile(g, DET_QUANTILE)
        a["det_gap_max"] = gap_quantile(g, 1.0)
        a["det_gaps"] = {"n": len(g), **{f"q{q:g}": gap_quantile(g, q)
                                         for q in QUANTILES}}
        a["det_worst"] = worst.get(side)
    return agg


def verdict(readings: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, [(name, value, limit)]) ; a reading passes when it
    is at most its limit."""
    rows = [(k, float(readings[k]), float(limits[k])) for k in NUMBERS]
    return all(v <= lim for _, v, lim in rows), rows
