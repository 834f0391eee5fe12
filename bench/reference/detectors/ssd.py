"""Detector family ``ssd``: the plain reference of the program's
``ssd-lite`` and ``ssd-deep``.

The configuration's ``detector`` block states the network: per block a
strided 3x3 convolution to ``channels[i]`` and ``extra_convs[i]`` more
3x3 convolutions, each with 'SAME' padding and ReLU, then a 1x1 head of
5 channels (an objectness logit and a box) on one grid of
``stride_px``-pixel cells.

A cell's box is the cell plus the regressed centre offset (clipped to
[0, 1]) and log-size (clipped to [-5, 5], in cell units), placed into
the frame by the window's origin and scale.  Nothing of the program is
imported.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import numpy as np

from bench.reference import nets


def forward(p, frames, det_cfg: dict, operands: Optional[str] = None):
    """frames (B, H, W, 3) -> (objectness logits (B, H/16, W/16),
    boxes (..., 4))."""
    return _forward(p, frames, channels=tuple(det_cfg["channels"]),
                    extra_convs=tuple(det_cfg["extra_convs"]),
                    operands=operands)


@functools.partial(jax.jit, static_argnames=("channels", "extra_convs",
                                             "operands"))
def _forward(p, frames, channels: Sequence[int], extra_convs: Sequence[int],
             operands: Optional[str] = None):
    x = frames
    for i in range(len(channels)):
        x = jax.nn.relu(nets.conv(x, p[f"block{i}_down/w"],
                                  p[f"block{i}_down/b"], 2, operands))
        for j in range(extra_convs[i]):
            x = jax.nn.relu(nets.conv(x, p[f"block{i}_conv{j}/w"],
                                      p[f"block{i}_conv{j}/b"], 1, operands))
    out = nets.conv(x, p["head/w"], p["head/b"], 1, operands)
    return out[..., 0], out[..., 1:]


def candidates(outputs, lo: float, origin, scale, det_cfg: dict
               ) -> np.ndarray:
    """One window's ``forward`` outputs -> (n, 5) frame boxes
    [cx, cy, w, h, logit] of every cell whose logit exceeds ``lo``."""
    logits, boxes = outputs
    hc, wc = logits.shape
    ii, jj = np.nonzero(logits > lo)
    lg = logits[ii, jj].astype(np.float64)
    bx = boxes[ii, jj].astype(np.float64)
    cx = origin[0] + (jj + np.clip(bx[:, 0], 0, 1)) / wc * scale[0]
    cy = origin[1] + (ii + np.clip(bx[:, 1], 0, 1)) / hc * scale[1]
    w = np.exp(np.clip(bx[:, 2], -5, 5)) / wc * scale[0]
    h = np.exp(np.clip(bx[:, 3], -5, 5)) / hc * scale[1]
    return np.stack([cx, cy, w, h, lg], axis=1).reshape(-1, 5)
