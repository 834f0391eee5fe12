"""Detector family ``ssd``, the program's side: the widths the program
registers for the configuration's ``arch``, and the operations and
bytes of one application (``bench/lib/flops.py``)."""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from bench.lib.flops import Layer, conv_bytes, conv_flops


def program_widths(det_cfg: dict) -> Dict[str, tuple]:
    """{name: (program value, file value)} of every width the file
    states for the detector."""
    from repro.core.detector import ARCHS, STRIDE
    chans, extras = ARCHS[det_cfg["arch"]]
    return {
        "detector.channels": (list(chans), det_cfg["channels"]),
        "detector.extra_convs": (list(extras), det_cfg["extra_convs"]),
        "detector.stride_px": (STRIDE, det_cfg["stride_px"]),
    }


def detector_layers(channels: Sequence[int], extra: Sequence[int],
                    head: int = 5) -> List[Layer]:
    out, cin = [], 3
    for c, e in zip(channels, extra):
        out.append((3, 2, cin, c))
        out.extend([(3, 1, c, c)] * e)
        cin = c
    out.append((1, 1, cin, head))
    return out


def work(det_cfg: dict, h: int, w: int) -> Tuple[float, float]:
    """(operations, bytes) of one application to an h x w input."""
    layers = detector_layers(det_cfg["channels"], det_cfg["extra_convs"])
    return conv_flops(layers, h, w), conv_bytes(layers, h, w)
