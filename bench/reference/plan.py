"""The proxy's cell decisions and the coverage a window plan must give.

The proxy scores 32 px cells of a nearest-neighbour downsample of the
frame.  A detector cell (16 px at the detector's resolution) is
positive when any proxy cell overlapping its span is; every positive
detector cell must lie inside one of the frame's planned windows (a
frame with no window has none positive).  The margin of a detector cell
is the largest ``score - threshold`` over its span: positive means the
reference marks it.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def downsample(frames: np.ndarray, res: Tuple[int, int]) -> np.ndarray:
    """Nearest neighbour: row i of the output is row i*H//h of the input."""
    w, h = res
    H, W = frames.shape[1:3]
    ys = (np.arange(h) * H) // h
    xs = (np.arange(w) * W) // w
    return frames[:, ys[:, None], xs[None, :]]


def spans(n_out: int, n_in: int):
    i = np.arange(n_out)
    lo = np.minimum((i * n_in) // n_out, n_in - 1)
    hi = np.minimum(((i + 1) * n_in + n_in - 1) // n_out, n_in)
    return lo, np.maximum(hi, lo + 1)


def cell_margins(scores: np.ndarray, threshold: float,
                 grid: Tuple[int, int]) -> np.ndarray:
    """(hp, wp) proxy scores -> (hc, wc) detector-cell margins."""
    wc, hc = grid
    hp, wp = scores.shape
    m = scores.astype(np.float64) - threshold
    ylo, yhi = spans(hc, hp)
    xlo, xhi = spans(wc, wp)
    rows = np.stack([m[ylo[i]:yhi[i]].max(axis=0) for i in range(hc)])
    return np.stack([rows[:, xlo[j]:xhi[j]].max(axis=1)
                     for j in range(wc)], axis=1)


def covered(windows: Sequence, grid: Tuple[int, int]) -> np.ndarray:
    """Detector cells inside any (x, y, (w, h)) window."""
    wc, hc = grid
    cov = np.zeros((hc, wc), bool)
    for x, y, (w, h) in windows:
        cov[y:y + h, x:x + w] = True
    return cov


def plan_gap(margins: np.ndarray, cov: np.ndarray) -> float:
    """Largest margin of a reference-positive cell the plan leaves out."""
    miss = (margins > 0) & ~cov
    return float(margins[miss].max()) if miss.any() else 0.0
