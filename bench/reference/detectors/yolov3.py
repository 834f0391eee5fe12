"""Detector family ``yolov3``: the plain reference of YOLOv3 on
Darknet-53 (Redmon and Farhadi, arXiv 1804.02767; darknet's
``cfg/yolov3.cfg``).

The configuration's ``detector`` block states the network: a 3x3 stem
conv of ``stem`` channels; per stage a stride-2 3x3 conv to
``stages[i]`` channels and ``repeats[i]`` residual blocks
``x + conv3x3(c)(conv1x1(c/2)(x))``; three heads of widths ``heads``
(strides 32, 16, 8), each 1x1 c, 3x3 2c, 1x1 c, 3x3 2c, 1x1 c (the
branch), 3x3 2c and a 1x1 output conv with bias to ``3 * (5 +
num_classes)``; between heads a 1x1 conv of the next head's width on the
branch, a 2x nearest upsample and a concat with the output of stage 3
(stride 16, darknet's layer 61) or stage 2 (stride 8, layer 36).  Every
other conv is followed by batch norm, applied here explicitly from the
stored statistics, ``(y - mean) / sqrt(var + bn_eps) * gamma + beta``,
and leaky ReLU of slope ``leaky_slope``.

Padding is darknet's: ``k // 2`` zeros on every side, output floored.
For a stride-1 conv that is ``nets.conv``'s 'SAME'.  For a stride-2
conv over an even input darknet's reads the top and left zero row where
'SAME' pads bottom and right.  Here the input is padded by one zero on
every side first: 'SAME' then adds only one more at the bottom and
right, output ``o`` reads input rows ``2o - 1 .. 2o + 1`` as darknet's
does, and the one extra last row and column of outputs is cropped.  (The
mirror image of a 'SAME' conv is darknet's too, but a v5e compile of a
conv between two reversals returned other numbers than the CPU.)

Letterbox: an input whose sides are not multiples of
``input_multiple_px`` (32) is padded bottom and right to the next
multiple with ``letterbox_fill`` (0.5); only cells whose origin lies
inside the unpadded input are candidates.

An anchor's score is ``p = sigmoid(obj) * sigmoid(cls[class_index])``,
its logit ``log p - log(1 - p)`` in float64; its box ``cx = (j +
sigmoid(tx)) * stride``, ``w = anchor_w * exp(tw)`` (and the same for y
and h), with no clip, in pixels of the input, placed into the frame by
the window's origin and scale.  Nothing of the program is imported.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import nets


def _spec(det: dict) -> tuple:
    return (int(det["stem"]), tuple(det["stages"]), tuple(det["repeats"]),
            tuple(det["heads"]), int(det["num_classes"]),
            int(det["class_index"]), float(det["leaky_slope"]),
            float(det["bn_eps"]), int(det["input_multiple_px"]),
            float(det["letterbox_fill"]), len(det["masks"][0]))


def forward(p, frames, det_cfg: dict, operands: Optional[str] = None):
    """frames (B, H, W, 3) -> per scale (stride 32, 16, 8) the
    objectness logits, ``class_index``'s logits (each (B, h, w, A)) and
    the box regressions (B, h, w, A, 4); last the inputs' unpadded
    (H, W), (B, 2)."""
    return _forward(p, frames, spec=_spec(det_cfg), operands=operands)


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def _forward(p, frames, spec: tuple, operands: Optional[str] = None):
    (_, _, repeats, heads, classes, cls, slope, eps, multiple, fill,
     A) = spec
    B, H, W, _ = frames.shape
    hp, wp = -(-H // multiple) * multiple, -(-W // multiple) * multiple
    x = jnp.pad(frames, ((0, 0), (0, hp - H), (0, wp - W), (0, 0)),
                constant_values=fill)

    def cbl(name, x, stride=1):
        w = p[f"{name}/w"]
        if stride == 1:
            y = nets.conv(x, w, 0.0, 1, operands)
        else:
            # every strided conv's input is even (the letterbox)
            h, wd = x.shape[1] // 2, x.shape[2] // 2
            y = nets.conv(jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0))), w,
                          0.0, stride, operands)[:, :h, :wd]
        y = (y - p[f"{name}/mean"]) / jnp.sqrt(p[f"{name}/var"] + eps) \
            * p[f"{name}/gamma"] + p[f"{name}/beta"]
        return jnp.where(y > 0, y, slope * y)

    x = cbl("stem", x)
    taps = []
    for i, r in enumerate(repeats):
        x = cbl(f"s{i}_down", x, 2)
        for j in range(r):
            x = x + cbl(f"s{i}_r{j}_b", cbl(f"s{i}_r{j}_a", x))
        taps.append(x)
    routes = (None, taps[3], taps[2])
    out = []
    x, branch = taps[4], None
    for k in range(len(heads)):
        if k > 0:
            up = cbl(f"h{k}_up", branch)
            up = jnp.repeat(jnp.repeat(up, 2, axis=1), 2, axis=2)
            x = jnp.concatenate([up, routes[k]], axis=-1)
        for m in range(5):
            x = cbl(f"h{k}_c{m}", x)
        branch = x
        y = nets.conv(cbl(f"h{k}_c5", x), p[f"h{k}_out/w"],
                      p[f"h{k}_out/b"], 1, operands)
        y = y.reshape(y.shape[:3] + (A, 5 + classes))
        out += [y[..., 4], y[..., 5 + cls], y[..., :4]]
    hw = jnp.broadcast_to(jnp.asarray([H, W], jnp.float32), (B, 2))
    return tuple(out) + (hw,)


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x))


def candidates(outputs, lo: float, origin, scale, det_cfg: dict
               ) -> np.ndarray:
    """One window's ``forward`` outputs -> (n, 5) frame boxes
    [cx, cy, w, h, logit] of every anchor whose cell origin lies inside
    the window and whose logit exceeds ``lo``."""
    *per, hw = outputs
    H, W = float(hw[0]), float(hw[1])
    anchors = np.asarray(det_cfg["anchors"], np.float64)
    rows = []
    for lvl, (s, mask) in enumerate(zip(det_cfg["strides"],
                                        det_cfg["masks"])):
        obj, cls, box = (np.asarray(a, np.float64)
                         for a in per[3 * lvl:3 * lvl + 3])
        p = _sigmoid(obj) * _sigmoid(cls)
        with np.errstate(divide="ignore"):
            lg = np.log(p) - np.log1p(-p)
        h, w, _ = obj.shape
        ii, jj, aa = np.nonzero(lg > lo)
        ok = (ii * s < H) & (jj * s < W)
        ii, jj, aa = ii[ok], jj[ok], aa[ok]
        t = box[ii, jj, aa]
        an = anchors[np.asarray(mask)][aa]
        cx = origin[0] + (jj + _sigmoid(t[:, 0])) * s / W * scale[0]
        cy = origin[1] + (ii + _sigmoid(t[:, 1])) * s / H * scale[1]
        bw = an[:, 0] * np.exp(t[:, 2]) / W * scale[0]
        bh = an[:, 1] * np.exp(t[:, 3]) / H * scale[1]
        rows.append(np.stack([cx, cy, bw, bh, lg[ii, jj, aa]], axis=1))
    return np.concatenate(rows).reshape(-1, 5)
