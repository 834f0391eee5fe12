"""Plain non-maximum suppression and the detection comparison.

Decoding a window's head outputs into candidate boxes is the detector
family's job (``candidates`` in ``bench/reference/detectors/<family>.py``):
rows of [cx, cy, w, h, logit] in frame units.  A candidate fires when
its score (the sigmoid of its logit) exceeds the confidence.
Per window the candidates are ranked by score, at most ``4 * max_dets``
are kept, greedy NMS at ``nms_iou`` runs and ``max_dets`` survive; a
frame planned as several windows merges them and runs NMS once more.

The comparison pairs each of the program's detections with the
reference's nearest candidate box, fired or not, kept or suppressed:
the same cell decoded twice.  Two neighbouring cells of one object,
whose NMS order a rounding can flip, are then told apart, and the flip
reads as the small margin that decided it.  Scores are compared as
logits clipped at +-9: the program's float32 sigmoid on the TPU holds a
score near 1 only to about 1e-5 (it read 1.0 for a reference logit of
12), so two scores beyond the clip tie, as they can in the program's
own NMS; every threshold of the menus lies within +-1.1.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

NEAR = 4.0         # candidates this many logits below the confidence
SATURATE = 9.0     # scores beyond this logit tie (see above)

BELOW, KEPT, SUPPRESSED = 0, 1, 2
STATUS = ("below", "kept", "suppressed")


def logit(p) -> np.ndarray:
    """Clipped logit of float32 scores."""
    p = np.clip(np.asarray(p, np.float64), 1e-30, 1.0)
    with np.errstate(divide="ignore"):
        x = np.log(p) - np.log1p(-p)
    return np.clip(x, -SATURATE, SATURATE)


def iou(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(n, >=4), (m, >=4) [cx, cy, w, h] -> (n, m)."""
    a = np.asarray(a, np.float64)[:, :4]
    b = np.asarray(b, np.float64)[:, :4]
    if len(a) == 0 or len(b) == 0:
        return np.zeros((len(a), len(b)))
    lo = np.maximum(a[:, None, :2] - a[:, None, 2:] / 2,
                    b[None, :, :2] - b[None, :, 2:] / 2)
    hi = np.minimum(a[:, None, :2] + a[:, None, 2:] / 2,
                    b[None, :, :2] + b[None, :, 2:] / 2)
    inter = np.prod(np.clip(hi - lo, 0, None), axis=2)
    union = (a[:, 2] * a[:, 3])[:, None] + (b[:, 2] * b[:, 3])[None] - inter
    return np.where(union > 0, inter / np.where(union > 0, union, 1), 0.0)


def nms(dets: np.ndarray, thr: float):
    """Greedy NMS over ``dets`` (column 4 ranks) -> (ranked rows, kept
    indices into them, suppressor index per row or -1)."""
    order = np.argsort(-dets[:, 4], kind="stable")
    d = dets[order]
    m = iou(d, d)
    keep: List[int] = []
    by = np.full(len(d), -1)
    for i in range(len(d)):
        hit = [k for k in keep if m[i, k] > thr]
        if hit:
            by[i] = hit[0]
        else:
            keep.append(i)
    return d, keep, by


class FrameDetections:
    """The reference's detections of one frame under a given window
    plan, with every candidate box and the decision that dropped it.
    Rows carry the logit in column 4."""

    def __init__(self, conf: float, nms_iou: float, max_dets: int):
        self.lconf = float(np.log(conf) - np.log1p(-conf))
        self.lo = self.lconf - NEAR            # the candidates to decode
        self.nms_iou, self.max_dets = nms_iou, max_dets
        self.rows: List[np.ndarray] = []       # candidate boxes
        self.status: List[int] = []
        self.supp: List[np.ndarray] = []       # suppressor box (or nan)
        self.parts: List[List[int]] = []       # per window: kept rows
        self.final: List[int] = []

    def _add(self, row, status, supp=None) -> int:
        self.rows.append(np.asarray(row, np.float64))
        self.status.append(status)
        self.supp.append(np.full(5, np.nan) if supp is None else supp)
        return len(self.rows) - 1

    def add_window(self, cand: np.ndarray) -> None:
        """One window's decoded candidates, (n, 5) rows whose logit
        exceeds ``lo``."""
        for row in cand[cand[:, 4] <= self.lconf]:
            self._add(row, BELOW)
        fired = cand[cand[:, 4] > self.lconf]
        fired = fired[np.argsort(-fired[:, 4], kind="stable")]
        fired = fired[:self.max_dets * 4]
        ranked, keep, by = nms(fired, self.nms_iou)
        idx = {}
        for i in range(len(ranked)):
            if by[i] < 0:
                idx[i] = self._add(ranked[i], KEPT)
            else:
                self._add(ranked[i], SUPPRESSED, ranked[by[i]])
        self.parts.append([idx[i] for i in keep[:self.max_dets]])
        for i in keep[self.max_dets:]:          # past the per-window cap
            self.status[idx[i]] = SUPPRESSED
            self.supp[idx[i]] = ranked[keep[self.max_dets - 1]]

    def finish(self, merge: bool) -> np.ndarray:
        """The frame's final detections, as [cx, cy, w, h, score]; with
        ``merge`` the windows' detections go through NMS once more."""
        ids = [i for part in self.parts for i in part]
        if merge and ids:
            rows = np.stack([self.rows[i] for i in ids])
            order = np.argsort(-rows[:, 4], kind="stable")
            ranked, keep, by = nms(rows, self.nms_iou)
            for r, i in enumerate(order):
                if by[r] >= 0:
                    self.status[ids[i]] = SUPPRESSED
                    self.supp[ids[i]] = ranked[by[r]]
            ids = [ids[order[r]] for r in keep]
        self.final = ids
        if not ids:
            return np.zeros((0, 5))
        out = np.stack([self.rows[i] for i in ids])
        out[:, 4] = 1.0 / (1.0 + np.exp(-out[:, 4]))
        return out

    def candidates(self) -> np.ndarray:
        return np.stack(self.rows) if self.rows else np.zeros((0, 5))


def box_px(a: np.ndarray, b: np.ndarray, W: int, H: int) -> np.ndarray:
    """(n, m) largest coordinate difference of two box sets, in pixels."""
    d = np.abs(np.asarray(a)[:, None, :4] - np.asarray(b)[None, :, :4])
    return (d * np.asarray([W, H, W, H], np.float64)).max(axis=2)


def frame_gaps(prog: np.ndarray, ref: FrameDetections, nms_iou: float,
               W: int, H: int) -> Tuple[List[float], float, dict]:
    """(decision gap in logits of every program detection and of every
    reference detection the program misses, largest box difference in
    pixels, the largest gap's particulars) between the program's
    detections of one frame, [cx, cy, w, h, score] rows, and the
    reference's.

    Each program detection is paired with the reference's nearest
    candidate: the pixel distance of the two boxes is the box
    difference; their logit difference counts, and so does how far the
    candidate's own decision lay from going the program's way (below the
    confidence: its distance from it; suppressed: the IoU margin or
    logit margin to its suppressor, whichever is smaller).  Each
    reference detection that is no program detection's candidate is
    missing from the program: its margin is its distance above the
    confidence or, where a program detection may have suppressed it,
    that suppression's margin."""
    prog = np.asarray(prog, np.float64).reshape(-1, 5)
    lp = logit(prog[:, 4])
    cand = ref.candidates()
    lc = np.clip(cand[:, 4], -SATURATE, SATURATE) if len(cand) else cand
    status = np.asarray(ref.status, int)
    gaps: List[float] = []
    box = 0.0
    worst = {"gap": -1.0}
    paired = set()
    if len(prog) and not len(cand):
        return ([2 * SATURATE] * len(prog), float(max(W, H)),
                {"gap": 2 * SATURATE, "kind": "no_candidate"})
    if len(prog):
        dist = box_px(prog, cand, W, H)
        near = dist.argmin(axis=1)
        for a, c in enumerate(near):
            paired.add(int(c))
            box = max(box, float(dist[a, c]))
            g = abs(lp[a] - lc[c])
            if status[c] == BELOW:
                g = max(g, ref.lconf - cand[c, 4])
            elif status[c] == SUPPRESSED:
                k = ref.supp[c]
                lk = min(max(k[4], -SATURATE), SATURATE)
                g = max(g, max(0.0, min(iou(cand[c][None], k[None])[0, 0]
                                        - nms_iou, lk - lc[c])))
            gaps.append(float(g))
            if g > worst["gap"]:
                worst = {"gap": float(g), "kind": "paired",
                         "status": STATUS[status[c]],
                         "program_logit": float(lp[a]),
                         "reference_logit": float(cand[c, 4]),
                         "box_px": float(dist[a, c]),
                         "box": [float(v) for v in prog[a, :4]]}
    for r in ref.final:
        if r in paired:
            continue
        g = cand[r, 4] - ref.lconf
        if len(prog):
            o = iou(cand[r][None], prog)[0]
            for a in np.nonzero(o > nms_iou)[0]:
                g = min(g, max(0.0, min(o[a] - nms_iou,
                                        abs(lp[a] - lc[r]))))
        gaps.append(float(g))
        if g > worst["gap"]:
            worst = {"gap": float(g), "kind": "missed",
                     "reference_logit": float(cand[r, 4]),
                     "box": [float(v) for v in cand[r, :4]]}
    return gaps, box, worst
