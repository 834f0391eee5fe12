"""Single-shot object detectors (the pipeline's expensive model).

The paper treats the detector as a pluggable black box with an
(architecture, input resolution) menu (YOLOv3 / Mask R-CNN at several
resolutions).  Two families are registered in ``FAMILIES``, each an
architecture menu the tuner can choose from and each bringing its
parameters, device forward, host decode and training (``Family``):

* ``ssd`` (``ssd-lite``, ``ssd-deep``, widths in ``ARCHS``): a strided
  conv backbone to stride ``STRIDE`` (16), then a 1x1 head predicting
  per cell [objectness, dx, dy, log w, log h].  A cell is positive when
  an object center falls inside it; boxes are regressed relative to the
  cell (center offset in [0,1]) and the frame (log-size).
* ``yolov3`` (``yolov3``, the paper's own detector at darknet's
  published widths, and ``yolov3-r16`` for CPU tests): Darknet-53 with
  three anchor heads at strides 32, 16 and 8, batch norm folded into
  the convs when a ``Detector`` is built (``repro.core.yolov3``).

Either network applies to full frames AND to the proxy-selected windows
(any HxW divisible by 16; YOLOv3 letterboxes to 32) — one jit
specialization per input size and power-of-two batch bucket, which is
exactly the paper's "initialize the detector at each of the k fixed
window sizes" with the chunked engine's cross-frame batching layered on
top.  Every family's device forward runs inside ``_detect_scores``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import yolov3
from repro.models.common import ParamBuilder, build
from repro.obs.trace import NO_SPAN, TRACER

STRIDE = 16

ARCHS: Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]] = {
    # name -> (channels per block, extra 3x3 convs per block)
    "ssd-lite": ((12, 24, 48, 96), (0, 0, 0, 0)),
    "ssd-deep": ((16, 32, 64, 128), (1, 1, 1, 1)),
}


def _conv(pb: ParamBuilder, name: str, cin: int, cout: int, k: int = 3
          ) -> None:
    with pb.scope(name):
        pb.param("w", (k, k, cin, cout), (None, None, None, "mlp"),
                 scale=(1.0 / np.sqrt(k * k * cin)))
        pb.param("b", (cout,), (None,), init="zeros")


def _apply_conv(p, x, stride: int = 1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _def_ssd(pb: ParamBuilder, arch: str) -> None:
    chans, extras = ARCHS[arch]
    cin = 3
    for i, (c, extra) in enumerate(zip(chans, extras)):
        _conv(pb, f"block{i}_down", cin, c)
        for j in range(extra):
            _conv(pb, f"block{i}_conv{j}", c, c)
        cin = c
    _conv(pb, "head", cin, 5, k=1)


@functools.partial(jax.jit, static_argnames=("arch",))
def detector_raw(params, frames, arch: str):
    """frames: (B, H, W, 3) -> (B, H/S, W/S, 5) raw head outputs."""
    chans, extras = ARCHS[arch]
    x = frames
    for i in range(len(chans)):
        x = jax.nn.relu(_apply_conv(params[f"block{i}_down"], x, stride=2))
        for j in range(extras[i]):
            x = jax.nn.relu(_apply_conv(params[f"block{i}_conv{j}"], x))
    return _apply_conv(params["head"], x)


def detector_loss(params, frames, obj_target, box_target, arch: str):
    """obj_target: (B, Hc, Wc) {0,1}; box_target: (B, Hc, Wc, 4)."""
    out = detector_raw(params, frames, arch)
    obj_logit = out[..., 0]
    box = out[..., 1:]
    obj = obj_target.astype(jnp.float32)
    bce = jnp.maximum(obj_logit, 0) - obj_logit * obj \
        + jnp.log1p(jnp.exp(-jnp.abs(obj_logit)))
    # class-balanced normalization: positives are ~5-10% of cells, so a
    # plain mean starves them of gradient and confidences stall below any
    # usable threshold
    n_pos = jnp.maximum(obj.sum(), 1.0)
    n_neg = jnp.maximum((1 - obj).sum(), 1.0)
    bce = (bce * obj).sum() / n_pos + (bce * (1 - obj)).sum() / n_neg
    l1 = jnp.sum(jnp.abs(box - box_target) * obj[..., None]) \
        / (n_pos * 4)
    return bce + l1


def make_targets(boxes_list: List[np.ndarray], hc: int, wc: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """boxes: per-frame (n, >=4) [cx, cy, w, h] world units -> targets."""
    B = len(boxes_list)
    obj = np.zeros((B, hc, wc), np.float32)
    box = np.zeros((B, hc, wc, 4), np.float32)
    for b, boxes in enumerate(boxes_list):
        for row in boxes:
            cx, cy, w, h = row[:4]
            j = min(int(cx * wc), wc - 1)
            i = min(int(cy * hc), hc - 1)
            obj[b, i, j] = 1.0
            # sizes in CELL units: input-resolution invariant (an object's
            # pixel size is what the conv net sees, full frame or window)
            box[b, i, j] = [cx * wc - j, cy * hc - i,
                            np.log(max(w * wc, 1e-3)),
                            np.log(max(h * hc, 1e-3))]
    return obj, box


def decode_detections(scores: np.ndarray, boxes: np.ndarray,
                      conf: float, origin: Tuple[float, float] = (0.0, 0.0),
                      scale: Tuple[float, float] = (1.0, 1.0),
                      max_dets: int = 64) -> np.ndarray:
    """One frame's head outputs -> (n, 5) [cx, cy, w, h, score] world
    units.  origin/scale place a WINDOW's cells into the full frame:
    world = origin + cell_frac * scale."""
    hc, wc = scores.shape
    ii, jj = np.nonzero(scores > conf)
    if len(ii) == 0:
        return np.zeros((0, 5), np.float32)
    sc = scores[ii, jj]
    order = np.argsort(-sc)[:max_dets * 4]
    ii, jj, sc = ii[order], jj[order], sc[order]
    bx = boxes[ii, jj]
    cx = origin[0] + (jj + np.clip(bx[:, 0], 0, 1)) / wc * scale[0]
    cy = origin[1] + (ii + np.clip(bx[:, 1], 0, 1)) / hc * scale[1]
    w = np.exp(np.clip(bx[:, 2], -5, 5)) / wc * scale[0]
    h = np.exp(np.clip(bx[:, 3], -5, 5)) / hc * scale[1]
    dets = np.stack([cx, cy, w, h, sc], axis=1).astype(np.float32)
    return nms(dets)[:max_dets]


def _ssd_scores(params, frames, arch: str):
    """(B, Hc, Wc) objectness and (B, Hc, Wc, 4) cell-relative boxes."""
    out = detector_raw(params, frames, arch)
    return jax.nn.sigmoid(out[..., 0]), out[..., 1:]


def _ssd_decode(scores, boxes, conf, hw, arch, origin, scale, max_dets):
    return decode_detections(scores, boxes, conf, origin=origin,
                             scale=scale, max_dets=max_dets)


def _ssd_train(arch: str, params, sample, steps: int, lr: float):
    # train_models imports this module, so its generic fit comes late
    from repro.core.train_models import _fit

    def batches():
        for step in range(steps):
            frames, boxes = sample(step)
            obj, box = make_targets(boxes, frames.shape[1] // STRIDE,
                                    frames.shape[2] // STRIDE)
            yield (jnp.asarray(frames), jnp.asarray(obj), jnp.asarray(box))

    loss_fn = lambda p, f, o, b: detector_loss(p, f, o, b, arch)  # noqa
    return _fit(loss_fn, params, batches(), lr=lr)


def _yolov3_decode(scores, boxes, conf, hw, arch, origin, scale, max_dets):
    cand = yolov3.decode(scores, boxes, conf, hw, arch, origin=origin,
                         scale=scale, max_dets=max_dets)
    return nms(cand)[:max_dets]


@dataclass(frozen=True)
class Family:
    """What the program needs of one detector family.  ``scores`` runs
    inside ``_detect_scores``; the rest on the host."""
    archs: Mapping[str, object]   # architecture -> its widths
    def_params: Callable          # (ParamBuilder, arch)
    fold: Callable                # (stored params, arch) -> forward params
    scores: Callable              # (forward params, frames, arch)
    #                               -> device (scores, boxes) of a batch
    decode: Callable              # (scores, boxes, conf, (h, w), arch,
    #                               origin, scale, max_dets) of one window
    #                               -> (n, 5) detections after NMS
    counters: Callable            # (scores of a batch, conf, (h, w), arch)
    #                               -> the decode span's family counters
    train: Callable               # (arch, params, sample, steps, lr)
    #                               -> (params, losses)
    max_batch: Optional[int] = None   # frames one device call holds


FAMILIES: Dict[str, Family] = {
    "ssd": Family(ARCHS, _def_ssd, lambda params, arch: params, _ssd_scores,
                  _ssd_decode, lambda scores, conf, hw, arch: {},
                  _ssd_train),
    "yolov3": Family(yolov3.SPECS, yolov3.def_params, yolov3.fold,
                     yolov3.scores, _yolov3_decode, yolov3.counters,
                     yolov3.train, max_batch=yolov3.MAX_BATCH),
}


def family_name(arch: str) -> str:
    """The name of the family an architecture belongs to."""
    for name, fam in FAMILIES.items():
        if arch in fam.archs:
            return name
    raise KeyError(f"unknown detector architecture {arch!r} (have "
                   f"{sorted(a for f in FAMILIES.values() for a in f.archs)})")


def family(arch: str) -> Family:
    """The family an architecture belongs to."""
    return FAMILIES[family_name(arch)]


def def_detector(pb: ParamBuilder, arch: str) -> None:
    family(arch).def_params(pb, arch)


def init_detector(arch: str, seed: int = 0):
    return build(functools.partial(def_detector, arch=arch), "init",
                 seed=seed)


def fold_params(params, arch: str):
    """The parameters the forward runs on: YOLOv3's batch norm folded
    into its convs; an ssd tree as it is."""
    return family(arch).fold(params, arch)


@functools.partial(jax.jit, static_argnames=("arch",))
def _detect_scores(params, frames, arch: str):
    """Device forward of every family -> (scores, boxes).  ssd: (B, Hc,
    Wc) objectness and (B, Hc, Wc, 4) cell-relative boxes; yolov3
    (folded parameters): (B, N) class-conditioned scores and (B, N, 4)
    raw anchor regressions over the three grids."""
    return family(arch).scores(params, frames, arch)


def nms(dets: np.ndarray, iou_thresh: float = 0.45) -> np.ndarray:
    if len(dets) <= 1:
        return dets
    order = np.argsort(-dets[:, 4])
    # one pairwise IoU matrix instead of O(n^2) scalar iou() calls;
    # greedy suppression order is unchanged
    m = iou_matrix(dets[order, :4], dets[order, :4])
    keep = []
    for i, idx in enumerate(order):
        if not keep or not (m[i, keep] > iou_thresh).any():
            keep.append(i)
    return dets[order[keep]]


def iou(a: np.ndarray, b: np.ndarray) -> float:
    ax0, ay0 = a[0] - a[2] / 2, a[1] - a[3] / 2
    ax1, ay1 = a[0] + a[2] / 2, a[1] + a[3] / 2
    bx0, by0 = b[0] - b[2] / 2, b[1] - b[3] / 2
    bx1, by1 = b[0] + b[2] / 2, b[1] + b[3] / 2
    ix = max(0.0, min(ax1, bx1) - max(ax0, bx0))
    iy = max(0.0, min(ay1, by1) - max(ay0, by0))
    inter = ix * iy
    union = a[2] * a[3] + b[2] * b[3] - inter
    return inter / union if union > 0 else 0.0


def iou_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """a: (n,4), b: (m,4) [cx,cy,w,h] -> (n,m) IoU."""
    if len(a) == 0 or len(b) == 0:
        return np.zeros((len(a), len(b)), np.float32)
    ax0 = a[:, 0] - a[:, 2] / 2
    ay0 = a[:, 1] - a[:, 3] / 2
    ax1 = a[:, 0] + a[:, 2] / 2
    ay1 = a[:, 1] + a[:, 3] / 2
    bx0 = b[:, 0] - b[:, 2] / 2
    by0 = b[:, 1] - b[:, 3] / 2
    bx1 = b[:, 0] + b[:, 2] / 2
    by1 = b[:, 1] + b[:, 3] / 2
    ix = np.maximum(0, np.minimum(ax1[:, None], bx1[None]) -
                    np.maximum(ax0[:, None], bx0[None]))
    iy = np.maximum(0, np.minimum(ay1[:, None], by1[None]) -
                    np.maximum(ay0[:, None], by0[None]))
    inter = ix * iy
    union = (a[:, 2] * a[:, 3])[:, None] + (b[:, 2] * b[:, 3])[None] - inter
    return np.where(union > 0, inter / union, 0.0).astype(np.float32)


def next_bucket(n: int, min_bucket: int = 1) -> int:
    """Smallest power-of-two >= n (>= min_bucket).  Batch dims are padded
    to these buckets so jit specializations stay one per
    (arch, input size, bucket) instead of one per exact batch count."""
    b = max(1, min_bucket)
    while b < n:
        b *= 2
    return b


def pad_to_bucket(arr: np.ndarray, min_bucket: int = 1) -> np.ndarray:
    """Zero-pad arr's leading (batch) dim to the next power-of-two
    bucket.  Returns arr unchanged when already bucket-sized."""
    n = int(arr.shape[0])
    b = next_bucket(n, min_bucket)
    if b == n:
        return arr
    padded = np.zeros((b,) + tuple(arr.shape[1:]),
                      np.asarray(arr).dtype)
    padded[:n] = arr
    return padded


class Detector:
    """Stateful wrapper: params + arch + jit cache per input size."""

    def __init__(self, arch: str, params=None, seed: int = 0):
        self.arch = arch
        self.family = family(arch)
        self.params = params if params is not None else init_detector(
            arch, seed)
        # what the forward runs on: YOLOv3's batch norm folded, once
        self.forward_params = fold_params(self.params, arch)
        # dispatch counter: the track store's re-ingest guarantee
        # ("zero detector calls on a warm split") is asserted against it.
        # Kept a plain per-instance int (benches reset it directly); each
        # increment also folds into the global obs registry.
        self.dispatches = 0
        from repro.obs.metrics import REGISTRY
        self._m_dispatches = REGISTRY.counter("detector.dispatches")

    def detect_batch(self, frames: np.ndarray, conf: float,
                     origins=None, scales=None, max_dets: int = 64,
                     n_valid: Optional[int] = None) -> List[np.ndarray]:
        """frames: (B, H, W, 3) -> list of (n, 5) world-unit detections.

        origins/scales: per-frame window placement (see
        decode_detections); default full frame.  n_valid: decode only the
        first n_valid rows (the rest are bucket padding).  Host frames
        are uploaded here; device frames (window crops) are not."""
        self.dispatches += 1
        self._m_dispatches.inc()
        n = frames.shape[0] if n_valid is None else n_valid
        x = frames
        if isinstance(frames, np.ndarray):
            with TRACER.span("detect.upload", "detect",
                             args={"h2d_bytes": frames.nbytes}) \
                    if TRACER.enabled else NO_SPAN:
                x = jnp.asarray(frames)
        with TRACER.span("detect.wait", "detect") \
                if TRACER.enabled else NO_SPAN:
            scores, boxes = self._scores(x)
            del x       # an uploaded batch is freed with its computation
            scores = np.asarray(scores)
            hit = (scores[:n] > conf).any(axis=tuple(range(1, scores.ndim)))
            boxes = np.asarray(boxes) if hit.any() else None
            if TRACER.enabled:
                TRACER.add("d2h_bytes", scores.nbytes + (
                    0 if boxes is None else boxes.nbytes))
        with TRACER.span("detect.decode", "detect", args={"windows": n}) \
                if TRACER.enabled else NO_SPAN as sp:
            empty = np.zeros((0, 5), np.float32)
            hw = tuple(frames.shape[1:3])
            out = [self.family.decode(
                scores[b], boxes[b], conf, hw, self.arch,
                origins[b] if origins is not None else (0.0, 0.0),
                scales[b] if scales is not None else (1.0, 1.0), max_dets)
                if hit[b] else empty for b in range(n)]
            if sp is not None:
                sp.args["dets"] = sum(len(d) for d in out)
                sp.args["candidates"] = int((scores[:n] > conf).sum())
                sp.args.update(self.family.counters(scores[:n], conf, hw,
                                                    self.arch))
        return out

    def _scores(self, x):
        """``_detect_scores`` over the batch, in slices of the family's
        ``max_batch`` where it has one."""
        step = self.family.max_batch
        if step is None or x.shape[0] <= step:
            return _detect_scores(self.forward_params, x, self.arch)
        parts = [_detect_scores(self.forward_params, x[i:i + step],
                                self.arch)
                 for i in range(0, x.shape[0], step)]
        return tuple(jnp.concatenate(p) for p in zip(*parts))

    def detect_batch_bucketed(self, frames: np.ndarray, conf: float,
                              origins=None, scales=None,
                              max_dets: int = 64) -> List[np.ndarray]:
        """detect_batch with the batch dim zero-padded to a power-of-two
        bucket.  Padding rows are never decoded; conv outputs are
        per-sample independent, so real rows are bit-identical to an
        unpadded call."""
        n = int(frames.shape[0])
        if n == 0:
            return []
        return self.detect_batch(pad_to_bucket(frames), conf,
                                 origins=origins, scales=scales,
                                 max_dets=max_dets, n_valid=n)


def detect_jit_entries() -> int:
    """Number of live jit specializations of the detector forward pass —
    the benchmark's bound is one per (arch, input size, bucket).
    Returns -1 when jax stops exposing the (private) cache-size hook."""
    cache_size = getattr(_detect_scores, "_cache_size", None)
    return int(cache_size()) if cache_size is not None else -1
