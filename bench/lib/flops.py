"""Operations and bytes of the pipeline's convolutions, from shapes.

A 'SAME' k x k convolution of stride s from (h, w, cin) to cout makes
one multiply-add per output position, kernel tap that lands inside the
input (taps on the zero padding are not work), input channel and output
channel, two operations each; the bias and activation are left out.  The least bytes a call
must move are its input image, its weights and its final outputs, so
intermediate activations kept on chip cost nothing: both counts err
low, and a roofline share built on them errs low too.

The detector's layer list lives with its family
(``bench/lib/detectors/<family>.py``, whose ``work`` is built on
``conv_flops`` and ``conv_bytes``); the proxy's is here.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

from bench.lib.registry import ROOT, find_family

F32 = 4

Layer = Tuple[int, int, int, int]      # (k, stride, cin, cout)


def proxy_layers(cell: int, base: int) -> List[Layer]:
    n = int(round(math.log2(cell)))
    out, cin = [], 3
    for i in range(n):
        c = base * min(2 ** i, 8)
        out.append((3, 2, cin, c))
        cin = c
    out.append((3, 1, cin, cin))
    out.append((1, 1, cin, 1))            # the 1x1 scoring head
    return out


def _taps(n: int, k: int, s: int) -> Tuple[int, int]:
    """(outputs, kernel taps inside the input summed over outputs) of a
    'SAME' 1-D convolution over ``n`` inputs."""
    out = math.ceil(n / s)
    lo = max((out - 1) * s + k - n, 0) // 2
    taps = sum(min(o * s - lo + k, n) - max(o * s - lo, 0)
               for o in range(out))
    return out, taps


def conv_flops(layers: Sequence[Layer], h: int, w: int) -> float:
    total = 0.0
    for k, s, cin, cout in layers:
        h, th = _taps(h, k, s)
        w, tw = _taps(w, k, s)
        total += 2.0 * th * tw * cin * cout
    return total


def conv_bytes(layers: Sequence[Layer], h: int, w: int) -> float:
    weights = sum(k * k * cin * cout + cout for k, _, cin, cout in layers)
    oh, ow = h, w
    for _, s, _, _ in layers:
        oh, ow = math.ceil(oh / s), math.ceil(ow / s)
    return F32 * (h * w * 3 + weights + oh * ow * layers[-1][3])


def detector_work(config: dict, theta: dict, sizes_cells, counters: dict,
                  root: str = ROOT) -> Tuple[float, float]:
    """(operations, bytes) of the detector over the window: every
    full-frame application at the detector's resolution, and every
    sub-frame window at the SMALLEST size of the set (the program counts
    windows, not windows per size), so both are lower bounds.  One
    application's counts are the family's ``work``."""
    d = config["detector"]
    work = find_family(d["family"], root).program.work
    W, H = theta["det_res"]
    cell = d["cell_px"]
    full = counters["full_frames"]
    sub = counters["detector_windows"] - full
    grid = (W // cell, H // cell)
    small = min((s for s in sizes_cells if tuple(s) != grid),
                key=lambda s: s[0] * s[1], default=grid)
    sw, sh = small[0] * cell, small[1] * cell
    full_ops, full_bytes = work(d, H, W)
    sub_ops, sub_bytes = work(d, sh, sw)
    return full * full_ops + sub * sub_ops, full * full_bytes + sub * sub_bytes


def proxy_work(config: dict, theta: dict, counters: dict
               ) -> Tuple[float, float]:
    p = config["proxy"]
    layers = proxy_layers(p["cell"], p["base_channels"])
    w, h = theta["proxy_res"]
    n = counters["frames_processed"]
    return n * conv_flops(layers, h, w), n * conv_bytes(layers, h, w)
