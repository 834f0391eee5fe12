"""Detector family ``yolov3``, the program's side: the widths the
program registers for the configuration's ``arch``
(``repro.core.yolov3.SPECS``), and the operations and bytes of one
application (``bench/lib/flops.py``).

Each of the 75 convs is counted at its own input size, on the
letterboxed input (sides padded up to ``input_multiple_px``; the padded
pixels are input the network computes on).  A stride-2 conv pads
darknet's way (top and left for an even side), the mirror image of
'SAME', so ``flops.conv_flops``' 'SAME' tap count is its count too.
Bytes: the letterboxed input image, the outputs the program pulls (a
score and four box numbers per anchor), and the weights once per device
call of up to ``BATCH`` windows (the program's largest call), so a
``BATCH``-th per application: the count errs low.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

from bench.lib.flops import F32, Layer, conv_flops

BATCH = 16


def program_widths(det_cfg: dict) -> Dict[str, tuple]:
    """{name: (program value, file value)} of every width the file
    states for the detector."""
    from repro.core.pipeline import CELL_PX
    from repro.core.yolov3 import SPECS, layers
    s = SPECS[det_cfg["arch"]]
    bn = sum(1 for *_, b in layers(s) if b)
    params = sum(k * k * ci * co + (2 * co if b else co)
                 for _, k, _, ci, co, b in layers(s))
    return {
        "detector.stem": (s.stem, det_cfg["stem"]),
        "detector.stages": (list(s.stages), det_cfg["stages"]),
        "detector.repeats": (list(s.repeats), det_cfg["repeats"]),
        "detector.heads": (list(s.heads), det_cfg["heads"]),
        "detector.anchors": ([list(a) for a in s.anchors],
                             det_cfg["anchors"]),
        "detector.masks": ([list(m) for m in s.masks], det_cfg["masks"]),
        "detector.strides": (list(s.strides), det_cfg["strides"]),
        "detector.routes": (_route_layers(s.repeats), det_cfg["routes"]),
        "detector.cell_px": (CELL_PX, det_cfg["cell_px"]),
        "detector.num_classes": (s.num_classes, det_cfg["num_classes"]),
        "detector.class_index": (s.class_index, det_cfg["class_index"]),
        "detector.leaky_slope": (s.leaky, det_cfg["leaky_slope"]),
        "detector.bn_eps": (s.bn_eps, det_cfg["bn_eps"]),
        "detector.input_multiple_px": (s.multiple,
                                       det_cfg["input_multiple_px"]),
        "detector.letterbox_fill": (s.fill, det_cfg["letterbox_fill"]),
        "detector.convs": (len(layers(s)), det_cfg["convs"]),
        "detector.batch_norms": (bn, det_cfg["convs"] - len(s.heads)),
        "detector.parameters": (params, det_cfg["parameters"]),
    }


def _route_layers(repeats) -> List[int]:
    """darknet's layer indices of the outputs of the 512- and 256-channel
    stages, which the second and third heads concat: the stem is layer
    0, a stage its strided conv and three layers a residual block (two
    convs and the shortcut)."""
    ends, n = [], 0
    for r in repeats:
        n += 1 + 3 * r
        ends.append(n)
    return [ends[3], ends[2]]


def _letterboxed(h: int, w: int, multiple: int) -> Tuple[int, int]:
    return -(-h // multiple) * multiple, -(-w // multiple) * multiple


def conv_layers(det: dict, h: int, w: int
                ) -> Iterator[Tuple[Layer, int, int]]:
    """Every conv as (layer, input h, input w), in network order, for an
    h x w input already letterboxed."""
    out = 3 * (5 + det["num_classes"])
    cin = det["stem"]
    yield (3, 1, 3, cin), h, w
    for c, r in zip(det["stages"], det["repeats"]):
        yield (3, 2, cin, c), h, w
        h, w = -(-h // 2), -(-w // 2)
        for _ in range(r):
            yield (1, 1, c, c // 2), h, w
            yield (3, 1, c // 2, c), h, w
        cin = c
    routes = (None, det["stages"][3], det["stages"][2])
    prev = None
    for k, (c, s) in enumerate(zip(det["heads"], det["strides"])):
        if k > 0:
            yield (1, 1, prev, c), h, w
            h, w = 2 * h, 2 * w
            cin = c + routes[k]
        for m in range(5):
            yield ((1, 1, cin, c) if m == 0 else
                   (1, 1, 2 * c, c) if m % 2 == 0 else
                   (3, 1, c, 2 * c)), h, w
        yield (3, 1, c, 2 * c), h, w
        yield (1, 1, 2 * c, out), h, w
        prev = c


def layer_list(det: dict) -> List[Layer]:
    return [layer for layer, _, _ in conv_layers(det, 32, 32)]


def work(det_cfg: dict, h: int, w: int) -> Tuple[float, float]:
    """(operations, bytes) of one application to an h x w input."""
    hp, wp = _letterboxed(h, w, det_cfg["input_multiple_px"])
    ops = sum(conv_flops([layer], lh, lw)
              for layer, lh, lw in conv_layers(det_cfg, hp, wp))
    anchors = sum((hp // s) * (wp // s) * len(m)
                  for s, m in zip(det_cfg["strides"], det_cfg["masks"]))
    weights = sum(k * k * ci * co + co for k, _, ci, co in
                  layer_list(det_cfg))
    return ops, F32 * (hp * wp * 3 + anchors * 5 + weights / BATCH)
