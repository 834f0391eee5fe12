"""The recurrent tracker, replayed on the program's own decisions.

Each detection's crop (a nearest-neighbour 16x16 resample of its box)
goes through the crop CNN; a projection adds the box and the frames
elapsed; a GRU folds a track's detections into its state; an MLP scores
(track, detection) pairs, and an assignment that first matches as many
pairs as score at least the match threshold, then the cheapest by
``1 - p``, continues tracks.  Unmatched detections start tracks;
a track unmatched more than ``max_misses`` frames in a row ends, and
at most ``max_tracks`` stay active (the longest).

The replay is teacher-forced: which detection continued which track is
read from the program's output tracks, so the reference's states follow
the program's history and one disagreement cannot cascade.  At every
frame the reference scores the pairs afresh, and the frame's reading is
the smallest change ``delta`` of the reference's probabilities (raised
on the program's pairs, lowered on the others) under which the
assignment rule picks exactly the program's pairs: 0 where it already
does.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from scipy.optimize import linear_sum_assignment

FORBIDDEN = 1e6


def _round(a: np.ndarray, operands: Optional[str]) -> np.ndarray:
    if operands is None:
        return a
    import ml_dtypes
    return np.asarray(a, np.float32).astype(
        getattr(ml_dtypes, operands)).astype(np.float64)


def _mm(a, b, operands):
    return _round(a, operands) @ _round(b, operands)


def _sig(x):
    return 1.0 / (1.0 + np.exp(-x))


class Heads:
    """The tracker's small heads in float64 (or with rounded operands)."""

    def __init__(self, weights: Dict[str, np.ndarray],
                 operands: Optional[str] = None):
        self.w = {k[len("tracker/"):]: np.asarray(v, np.float64)
                  for k, v in weights.items() if k.startswith("tracker/")}
        self.op = operands

    def feats(self, x, boxes, te):
        te = np.asarray(te, np.float64)
        extra = np.stack([boxes[:, 0], boxes[:, 1], boxes[:, 2],
                          boxes[:, 3], te / 8.0, np.log1p(te)], axis=1)
        d = np.concatenate([x, extra], axis=1)
        return np.tanh(_mm(d, self.w["det_proj/w"], self.op)
                       + self.w["det_proj/b"])

    def gru(self, h, f):
        w = self.w
        hf = np.concatenate([f, h], axis=1)
        z = _sig(_mm(hf, w["gru/wz"], self.op) + w["gru/bz"])
        r = _sig(_mm(hf, w["gru/wr"], self.op) + w["gru/br"])
        c = np.tanh(_mm(np.concatenate([f, r * h], axis=1), w["gru/wh"],
                        self.op) + w["gru/bh"])
        return (1 - z) * h + z * c

    def probs(self, hs, tboxes, feats, dboxes, te):
        w = self.w
        T, N = len(hs), len(feats)
        d = dboxes[None, :, :] - tboxes[:, None, :]
        rel = np.concatenate([d[..., :2], d[..., :2] / max(te, 1.0),
                              d[..., 2:]], axis=-1)
        pair = np.concatenate([
            np.broadcast_to(hs[:, None], (T, N, hs.shape[1])),
            np.broadcast_to(feats[None], (T, N, feats.shape[1])), rel],
            axis=-1).reshape(T * N, -1)
        hid = np.tanh(_mm(pair, w["match/w0"], self.op) + w["match/b0"])
        return _sig(_mm(hid, w["match/w1"], self.op)
                    + w["match/b1"]).reshape(T, N)


def rule(p: np.ndarray, thr: float) -> frozenset:
    cost = np.where(p >= thr, 1.0 - p, FORBIDDEN)
    r, c = linear_sum_assignment(cost)
    return frozenset((int(a), int(b)) for a, b in zip(r, c)
                     if cost[a, b] < FORBIDDEN / 2)


def decision_gap(p: np.ndarray, pairs: frozenset, thr: float,
                 steps: int = 24) -> float:
    """Smallest delta (to 1e-7) under which ``rule`` returns ``pairs``."""
    if rule(p, thr) == pairs:
        return 0.0
    mask = np.zeros(p.shape, bool)
    for a, b in pairs:
        mask[a, b] = True
    lo, hi = 0.0, 1.0
    for _ in range(steps):
        mid = (lo + hi) / 2
        if rule(np.where(mask, p + mid, p - mid), thr) == pairs:
            hi = mid
        else:
            lo = mid
    return hi


def crops(frame: np.ndarray, boxes: np.ndarray, C: int) -> np.ndarray:
    H, W = frame.shape[:2]
    if len(boxes) == 0:
        return np.zeros((0, C, C, 3), np.float32)
    b = np.asarray(boxes)[:, :4]
    x0, x1 = (b[:, 0] - b[:, 2] / 2) * W, (b[:, 0] + b[:, 2] / 2) * W
    y0, y1 = (b[:, 1] - b[:, 3] / 2) * H, (b[:, 1] + b[:, 3] / 2) * H
    xs = np.clip(np.linspace(x0, x1, C, axis=1).astype(np.int64), 0, W - 1)
    ys = np.clip(np.linspace(y0, y1, C, axis=1).astype(np.int64), 0, H - 1)
    return frame[ys[:, :, None], xs[:, None, :]]


def _key(f: int, box) -> Tuple:
    return int(f), np.asarray(box[:4], np.float32).tobytes()


class _Track:
    __slots__ = ("tid", "h", "frames", "boxes", "misses")

    def __init__(self, tid, h, f, box):
        self.tid, self.h = tid, h
        self.frames, self.boxes, self.misses = [f], [box], 0


def replay(frame_ids: Sequence[int], dets: Sequence[np.ndarray],
           feats_x: Sequence[np.ndarray], tracks: Sequence[np.ndarray],
           heads: Heads, tcfg: dict,
           control: Optional[Tuple[Heads, Sequence[np.ndarray]]] = None
           ) -> Dict[str, float]:
    """Replay one stream.  ``feats_x[k]`` are the reference's crop
    features of ``dets[k]``; ``control`` is (heads, crop features) of
    the control, whose own pairs are read against the reference.

    Returns track_gap (largest frame reading), rows_unexplained (output
    rows that are no detection of their frame, or that continue a track
    no longer active) and, with a control, control_track_gap."""
    thr = float(tcfg["match_threshold"])
    max_misses = int(tcfg["max_misses"])
    max_tracks = int(tcfg["max_tracks"])
    H = int(tcfg["rnn_dim"])
    owner: Dict[Tuple, int] = {}
    for tr in tracks:
        for row in tr:
            owner[_key(row[0], row[1:5])] = int(row[5])
    seen_rows = 0
    started: set = set()
    active: List[_Track] = []
    gap = cgap = 0.0
    unexplained = 0
    last = None
    pseudo = -1
    for k, f in enumerate(frame_ids):
        D = np.asarray(dets[k], np.float64).reshape(-1, 5)
        n = len(D)
        te = 0.0 if last is None else float(f - last)
        last = f
        boxes = D[:, :4]
        ids = []
        for row in D:
            tid = owner.get(_key(f, row[:4]))
            ids.append(tid)
            if tid is not None:
                seen_rows += 1
        pos = {t.tid: i for i, t in enumerate(active)}
        pairs, new = set(), []
        for di, tid in enumerate(ids):
            if tid is not None and tid in started:
                if tid in pos:
                    pairs.add((pos[tid], di))
                else:
                    unexplained += 1
                    new.append(di)
            else:
                new.append(di)
        T = len(active)
        if T and n:
            hs = np.stack([t.h for t in active])
            tb = np.stack([t.boxes[-1] for t in active])
            p = heads.probs(hs, tb, heads.feats(feats_x[k], boxes,
                                                np.full(n, te)), boxes, te)
            gap = max(gap, decision_gap(p, frozenset(pairs), thr))
            if control is not None:
                ch, cx = control
                pc = ch.probs(hs, tb, ch.feats(cx[k], boxes, np.full(n, te)),
                              boxes, te)
                cgap = max(cgap, decision_gap(p, rule(pc, thr), thr))
        # advance along the program's decisions
        upd = sorted(pairs)
        rows = [di for _, di in upd] + new
        if rows:
            te_u = [f - active[ti].frames[-1] for ti, _ in upd] \
                + [0.0] * len(new)
            h0 = np.zeros((len(rows), H))
            for r, (ti, _) in enumerate(upd):
                h0[r] = active[ti].h
            fx = heads.feats(feats_x[k][rows], boxes[rows], np.asarray(te_u))
            h1 = heads.gru(h0, fx)
        matched = {ti for ti, _ in upd}
        for r, (ti, di) in enumerate(upd):
            t = active[ti]
            t.h = h1[r]
            t.frames.append(f)
            t.boxes.append(boxes[di])
            t.misses = 0
        survivors = []
        for ti, t in enumerate(active):
            if ti not in matched:
                t.misses += 1
                if t.misses > max_misses:
                    continue
            survivors.append(t)
        active = survivors
        for r, di in enumerate(new, start=len(upd)):
            tid = ids[di]
            if tid is None or tid in started:
                tid, pseudo = pseudo, pseudo - 1
            started.add(tid)
            active.append(_Track(tid, h1[r], f, boxes[di]))
        if len(active) > max_tracks:
            active = sorted(active, key=lambda t: -len(t.frames))[:max_tracks]
    unexplained += sum(len(t) for t in tracks) - seen_rows
    out = {"track_gap": gap, "rows_unexplained": float(unexplained)}
    if control is not None:
        out["control_track_gap"] = cgap
    return out
