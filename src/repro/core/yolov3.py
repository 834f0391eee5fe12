"""YOLOv3 on Darknet-53 (Redmon and Farhadi, arXiv 1804.02767; widths as
darknet's ``cfg/yolov3.cfg`` states them): the detector family
``yolov3`` behind ``detector.Detector``.

Network.  A 3x3 stem conv, then five stages, each a stride-2 3x3 conv
followed by residual blocks ``x + conv3x3(c)(conv1x1(c/2)(x))``
(Darknet-53: 52 convs at the published widths).  Three heads at strides
32, 16 and 8, each five alternating 1x1/3x3 convs, a 3x3 conv and a
1x1 output conv with bias to ``3 * (5 + classes)`` channels; a 1x1 conv,
a 2x nearest upsample and a concat with the stage-4 (stride 16) and
stage-3 (stride 8) outputs join them.  Every conv but the three output
convs is conv (no bias), batch norm, leaky ReLU.  Padding is darknet's:
``k // 2`` zeros on every side and the output floored, so a stride-2
conv over an even input reads the top and left zero row (XLA's
``"SAME"`` would pad bottom and right).

Parameters are stored as trained: every batch-normed conv keeps ``w``
and ``gamma``/``beta``/``mean``/``var``.  ``fold`` turns them into a
plain ``w``/``b`` pair once (``Detector.__init__``); the forward runs on
the folded pairs.

Input.  The planner's windows are multiples of 16 px; the network needs
multiples of 32.  A window is letterboxed: padded bottom and right to
the next multiple of 32 with ``fill`` (0.5, darknet's
``letterbox_image`` grey), and only cells whose origin lies inside the
window are decoded (their scores are zeroed on the device).

Outputs (``scores``): per anchor the class-conditioned score
``sigmoid(obj) * sigmoid(cls[class_index])`` and the raw box regression
``(tx, ty, tw, th)``, the three grids flattened in the heads' order
(stride 32, 16, 8), each row-major over (row, column, anchor).  The host
decodes an anchor's box as ``cx = (j + sigmoid(tx)) * stride``,
``w = anchor_w * exp(tw)`` (``decode``), with no clip.

Training (``train``): each ground-truth box goes to the anchor of best
shape IoU, at that anchor's scale and cell; objectness and class
binary cross-entropy, squared error on ``sigmoid(tx), sigmoid(ty), tw,
th`` weighted by ``2 - w*h`` (darknet's small-box weight); a prediction
whose decoded box overlaps a ground-truth box by IoU > 0.5 is no
negative (paper section 2.1); batch norm in training mode, its running
statistics kept for inference.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ParamBuilder

# frames one device call holds: a 960x544 frame holds about 0.33 GB of
# activations, so 64 at once would not fit a 16 GB chip
MAX_BATCH = 16

ANCHORS = ((10, 13), (16, 30), (33, 23), (30, 61), (62, 45), (59, 119),
           (116, 90), (156, 198), (373, 326))


@dataclass(frozen=True)
class YoloSpec:
    stem: int
    stages: Tuple[int, ...]
    repeats: Tuple[int, ...]
    heads: Tuple[int, ...]               # 1x1 width of each head
    anchors: Tuple[Tuple[int, int], ...] = ANCHORS
    masks: Tuple[Tuple[int, ...], ...] = ((6, 7, 8), (3, 4, 5), (0, 1, 2))
    strides: Tuple[int, ...] = (32, 16, 8)
    num_classes: int = 80
    class_index: int = 2                 # COCO "car"
    leaky: float = 0.1
    bn_eps: float = 1e-5
    multiple: int = 32                   # input sides padded to this
    fill: float = 0.5                    # letterbox grey
    bn_momentum: float = 0.9             # running statistics, training

    @property
    def out_channels(self) -> int:
        return len(self.masks[0]) * (5 + self.num_classes)


SPECS: Dict[str, YoloSpec] = {
    "yolov3": YoloSpec(stem=32, stages=(64, 128, 256, 512, 1024),
                       repeats=(1, 2, 8, 8, 4), heads=(512, 256, 128)),
    # the same topology with every width divided by 16 and one residual
    # block a stage: CPU tests and the reduced pipeline
    "yolov3-r16": YoloSpec(stem=2, stages=(4, 8, 16, 32, 64),
                           repeats=(1, 1, 1, 1, 1), heads=(32, 16, 8)),
}


# ---------------------------------------------------------------------------
# Layers: (name, k, stride, cin, cout, batch-normed) in network order
# ---------------------------------------------------------------------------

def layers(spec: YoloSpec) -> List[Tuple[str, int, int, int, int, bool]]:
    out = [("stem", 3, 1, 3, spec.stem, True)]
    cin = spec.stem
    for i, (c, r) in enumerate(zip(spec.stages, spec.repeats)):
        out.append((f"s{i}_down", 3, 2, cin, c, True))
        for j in range(r):
            out.append((f"s{i}_r{j}_a", 1, 1, c, c // 2, True))
            out.append((f"s{i}_r{j}_b", 3, 1, c // 2, c, True))
        cin = c
    routes = (None, spec.stages[3], spec.stages[2])
    for k, c in enumerate(spec.heads):
        if k > 0:
            out.append((f"h{k}_up", 1, 1, spec.heads[k - 1], c, True))
            cin = c + routes[k]
        for m in range(5):
            out.append((f"h{k}_c{m}", 1 if m % 2 == 0 else 3, 1,
                        cin if m == 0 else (2 * c if m % 2 == 0 else c),
                        c if m % 2 == 0 else 2 * c, True))
        out.append((f"h{k}_c5", 3, 1, c, 2 * c, True))
        out.append((f"h{k}_out", 1, 1, 2 * c, spec.out_channels, False))
    return out


def def_params(pb: ParamBuilder, arch: str) -> None:
    for name, k, _, cin, cout, bn in layers(SPECS[arch]):
        with pb.scope(name):
            pb.param("w", (k, k, cin, cout), (None, None, None, "mlp"),
                     scale=float(np.sqrt(2.0 / (k * k * cin))))
            if bn:
                pb.param("gamma", (cout,), (None,), init="ones")
                pb.param("beta", (cout,), (None,), init="zeros")
                pb.param("mean", (cout,), (None,), init="zeros")
                pb.param("var", (cout,), (None,), init="ones")
            else:
                pb.param("b", (cout,), (None,), init="zeros")


def fold(params, arch: str):
    """Batch norm folded into each conv: ``w * g``, ``beta - mean * g``
    with ``g = gamma / sqrt(var + bn_eps)`` -> {name: {"w", "b"}}."""
    spec = SPECS[arch]
    eps = spec.bn_eps
    out = {}
    for name, *_, bn in layers(spec):
        p = params[name]
        if not bn:
            out[name] = {"w": jnp.asarray(p["w"]), "b": jnp.asarray(p["b"])}
            continue
        g = jnp.asarray(p["gamma"]) / jnp.sqrt(jnp.asarray(p["var"]) + eps)
        out[name] = {"w": jnp.asarray(p["w"]) * g,
                     "b": jnp.asarray(p["beta"]) - jnp.asarray(p["mean"]) * g}
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _conv(w, x, stride: int):
    k = w.shape[0]
    pad = k // 2
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _upsample(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def letterboxed(h: int, w: int, multiple: int = 32) -> Tuple[int, int]:
    """(h, w) padded up to the next multiple."""
    return -(-h // multiple) * multiple, -(-w // multiple) * multiple


def letterbox(frames, spec: YoloSpec):
    """Pad bottom and right to the next multiple of ``spec.multiple``
    with ``spec.fill``."""
    B, H, W, C = frames.shape
    hp, wp = letterboxed(H, W, spec.multiple)
    if (hp, wp) == (H, W):
        return frames
    return jnp.pad(frames, ((0, 0), (0, hp - H), (0, wp - W), (0, 0)),
                   constant_values=spec.fill)


def network(apply, x, spec: YoloSpec):
    """The Darknet-53 graph over ``apply(name, x, stride)`` (one conv
    with its normalisation and activation, or the linear output conv)
    -> the three heads' raw outputs (stride 32, 16, 8)."""
    x = apply("stem", x, 1)
    taps = []
    for i, r in enumerate(spec.repeats):
        x = apply(f"s{i}_down", x, 2)
        for j in range(r):
            x = x + apply(f"s{i}_r{j}_b", apply(f"s{i}_r{j}_a", x, 1), 1)
        taps.append(x)
    routes = (None, taps[3], taps[2])
    outs = []
    x, branch = taps[4], None
    for k in range(len(spec.heads)):
        if k > 0:
            x = jnp.concatenate([_upsample(apply(f"h{k}_up", branch, 1)),
                                 routes[k]], axis=-1)
        for m in range(5):
            x = apply(f"h{k}_c{m}", x, 1)
        branch = x
        outs.append(apply(f"h{k}_out", apply(f"h{k}_c5", x, 1), 1))
    return outs


def raw_outputs(folded, frames, arch: str):
    """frames (B, H, W, 3), any multiple of 16 -> the heads' raw outputs,
    each (B, h, w, anchors, 5 + classes), on the letterboxed input."""
    spec = SPECS[arch]
    bn = {name for name, *_, b in layers(spec) if b}

    def apply(name, x, stride):
        p = folded[name]
        y = _conv(p["w"], x, stride) + p["b"]
        return jax.nn.leaky_relu(y, spec.leaky) if name in bn else y

    outs = network(apply, letterbox(frames, spec), spec)
    A = len(spec.masks[0])
    return [o.reshape(o.shape[:3] + (A, 5 + spec.num_classes))
            for o in outs]


def inside(h: int, w: int, spec: YoloSpec) -> np.ndarray:
    """(N,) bool over the flattened anchors of an h x w window: the
    cell's origin lies inside the window (not on the letterbox)."""
    hp, wp = letterboxed(h, w, spec.multiple)
    A = len(spec.masks[0])
    parts = []
    for s in spec.strides:
        ii, jj = np.meshgrid(np.arange(hp // s), np.arange(wp // s),
                             indexing="ij")
        ok = (ii * s < h) & (jj * s < w)
        parts.append(np.repeat(ok.reshape(-1), A))
    return np.concatenate(parts)


def scores(folded, frames, arch: str):
    """The device side of ``_detect_scores``: frames (B, H, W, 3) ->
    (class-conditioned scores (B, N), raw boxes (B, N, 4)) over every
    anchor of the three grids; cells on the letterbox score 0."""
    spec = SPECS[arch]
    B, H, W, _ = frames.shape
    outs = raw_outputs(folded, frames, arch)
    flat = jnp.concatenate([o.reshape(B, -1, o.shape[-1]) for o in outs],
                           axis=1)
    c = 5 + spec.class_index
    sc = jax.nn.sigmoid(flat[..., 4]) * jax.nn.sigmoid(flat[..., c])
    ok = inside(H, W, spec)
    if not ok.all():
        sc = jnp.where(jnp.asarray(ok)[None], sc, 0.0)
    return sc, flat[..., :4]


# ---------------------------------------------------------------------------
# Anchor geometry (host)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def anchor_table(h: int, w: int, arch: str) -> np.ndarray:
    """(N, 6) float32 per flattened anchor of an h x w window:
    [column, row, stride, anchor w, anchor h, scale level]."""
    spec = SPECS[arch]
    hp, wp = letterboxed(h, w, spec.multiple)
    rows = []
    for lvl, (s, mask) in enumerate(zip(spec.strides, spec.masks)):
        ii, jj, aa = np.meshgrid(np.arange(hp // s), np.arange(wp // s),
                                 np.arange(len(mask)), indexing="ij")
        an = np.asarray(spec.anchors, np.float32)[np.asarray(mask)][aa]
        rows.append(np.stack([jj, ii, np.full(jj.shape, s), an[..., 0],
                              an[..., 1], np.full(jj.shape, lvl)],
                             axis=-1).reshape(-1, 6))
    return np.concatenate(rows).astype(np.float32)


def decode(scores_b: np.ndarray, boxes_b: np.ndarray, conf: float,
           hw: Tuple[int, int], arch: str, origin=(0.0, 0.0),
           scale=(1.0, 1.0), max_dets: int = 64) -> np.ndarray:
    """One window's outputs -> (n, 5) [cx, cy, w, h, score] candidates
    over ``conf``, the highest ``4 * max_dets`` of them, in frame units.
    ``hw`` is the window's size in pixels before the letterbox;
    origin/scale place it in the frame (world = origin + window fraction
    * scale)."""
    idx = np.nonzero(scores_b > conf)[0]
    if len(idx) == 0:
        return np.zeros((0, 5), np.float32)
    sc = scores_b[idx]
    order = np.argsort(-sc)[:max_dets * 4]
    idx, sc = idx[order], sc[order]
    t = boxes_b[idx]
    a = anchor_table(hw[0], hw[1], arch)[idx]
    H, W = hw
    sig = 1.0 / (1.0 + np.exp(-t[:, :2]))
    cx = origin[0] + (a[:, 0] + sig[:, 0]) * a[:, 2] / W * scale[0]
    cy = origin[1] + (a[:, 1] + sig[:, 1]) * a[:, 2] / H * scale[1]
    w = a[:, 3] * np.exp(t[:, 2]) / W * scale[0]
    h = a[:, 4] * np.exp(t[:, 3]) / H * scale[1]
    return np.stack([cx, cy, w, h, sc], axis=1).astype(np.float32)


def counters(scores: np.ndarray, conf: float, hw: Tuple[int, int],
             arch: str) -> Dict[str, int]:
    """A batch's decode counters: the anchors over ``conf`` per head
    stride (``cand_s<stride>``) and the windows letterboxed
    (``padded``)."""
    spec = SPECS[arch]
    level = anchor_table(hw[0], hw[1], arch)[:, 5].astype(np.int64)
    per_level = np.bincount(level, weights=(scores > conf).sum(axis=0),
                            minlength=len(spec.strides))
    out = {f"cand_s{s}": int(c) for s, c in zip(spec.strides, per_level)}
    out["padded"] = len(scores) if letterboxed(
        hw[0], hw[1], spec.multiple) != tuple(hw) else 0
    return out


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def _shape_iou(wh: np.ndarray, anchors: np.ndarray) -> np.ndarray:
    """(n, 2), (m, 2) box sizes, centres aligned -> (n, m) IoU."""
    inter = np.minimum(wh[:, None, 0], anchors[None, :, 0]) * \
        np.minimum(wh[:, None, 1], anchors[None, :, 1])
    union = (wh[:, 0] * wh[:, 1])[:, None] + \
        (anchors[:, 0] * anchors[:, 1])[None] - inter
    return inter / union


def make_targets(boxes_list: Sequence[np.ndarray], h: int, w: int,
                 arch: str, max_gt: int = 64):
    """Per-frame ground truth, (n, >=4) [cx, cy, w, h] frame units of an
    h x w input -> (objectness (B, N), box targets (B, N, 4) [x offset
    in the cell, y offset, log(w / anchor w), log(h / anchor h)], box
    weights (B, N) ``2 - w*h``, ground-truth boxes in pixels (B, G, 4)
    with a validity mask (B, G))."""
    spec = SPECS[arch]
    tab = anchor_table(h, w, arch)
    hp, wp = letterboxed(h, w, spec.multiple)
    offsets = {}
    off = 0
    for lvl, s in enumerate(spec.strides):
        offsets[lvl] = off
        off += (hp // s) * (wp // s) * len(spec.masks[lvl])
    level_of = {a: lvl for lvl, m in enumerate(spec.masks) for a in m}
    B, N = len(boxes_list), len(tab)
    obj = np.zeros((B, N), np.float32)
    tbox = np.zeros((B, N, 4), np.float32)
    wt = np.zeros((B, N), np.float32)
    gt = np.zeros((B, max_gt, 4), np.float32)
    gmask = np.zeros((B, max_gt), np.float32)
    anchors = np.asarray(spec.anchors, np.float32)
    for b, boxes in enumerate(boxes_list):
        boxes = np.asarray(boxes, np.float32).reshape(-1, 5)[:max_gt]
        if not len(boxes):
            continue
        px = boxes[:, :4] * np.asarray([w, h, w, h], np.float32)
        gt[b, :len(px)] = px
        gmask[b, :len(px)] = 1.0
        best = _shape_iou(np.maximum(px[:, 2:], 1e-3), anchors).argmax(1)
        for (cx, cy, bw, bh), a in zip(px, best):
            lvl = level_of[int(a)]
            s = spec.strides[lvl]
            A = len(spec.masks[lvl])
            gw = wp // s
            j = min(int(cx // s), gw - 1)
            i = min(int(cy // s), hp // s - 1)
            k = offsets[lvl] + (i * gw + j) * A + spec.masks[lvl].index(a)
            obj[b, k] = 1.0
            tbox[b, k] = [cx / s - j, cy / s - i,
                          np.log(max(bw, 1e-3) / anchors[a, 0]),
                          np.log(max(bh, 1e-3) / anchors[a, 1])]
            wt[b, k] = 2.0 - (bw * bh) / (w * h)
    return obj, tbox, wt, gt, gmask


def _train_outputs(params, frames, arch: str):
    """The forward with batch norm in training mode -> (the heads' raw
    outputs flattened (B, N, 5 + classes), the batch statistics)."""
    spec = SPECS[arch]
    stats = {}
    kind = {name: bn for name, *_, bn in layers(spec)}

    def apply(name, x, stride):
        p = params[name]
        y = _conv(p["w"], x, stride)
        if not kind[name]:
            return y + p["b"]
        mean = y.mean(axis=(0, 1, 2))
        var = y.var(axis=(0, 1, 2))
        stats[name] = (mean, var)
        y = (y - mean) * (p["gamma"] / jnp.sqrt(var + spec.bn_eps)) \
            + p["beta"]
        return jax.nn.leaky_relu(y, spec.leaky)

    outs = network(apply, letterbox(frames, spec), spec)
    B = frames.shape[0]
    flat = jnp.concatenate([o.reshape(B, -1, spec.out_channels
                                      // len(spec.masks[0]))
                            for o in outs], axis=1)
    return flat, stats


def _bce(logit, target):
    return jnp.maximum(logit, 0) - logit * target + \
        jnp.log1p(jnp.exp(-jnp.abs(logit)))


def loss(params, frames, obj, tbox, wt, gt, gmask, table, arch: str):
    """-> (loss per frame, batch statistics).  ``table`` is
    ``anchor_table`` of the input size."""
    spec = SPECS[arch]
    flat, stats = _train_outputs(params, frames, arch)
    t = flat[..., :4]
    sig = jax.nn.sigmoid(t[..., :2])
    # the decoded boxes, for the ignore rule (no gradient through it)
    cx = (table[:, 0] + sig[..., 0]) * table[:, 2]
    cy = (table[:, 1] + sig[..., 1]) * table[:, 2]
    bw = table[:, 3] * jnp.exp(jnp.clip(t[..., 2], -10.0, 10.0))
    bh = table[:, 4] * jnp.exp(jnp.clip(t[..., 3], -10.0, 10.0))
    pred = jax.lax.stop_gradient(jnp.stack([cx, cy, bw, bh], -1))
    lo = jnp.maximum(pred[:, :, None, :2] - pred[:, :, None, 2:] / 2,
                     gt[:, None, :, :2] - gt[:, None, :, 2:] / 2)
    hi = jnp.minimum(pred[:, :, None, :2] + pred[:, :, None, 2:] / 2,
                     gt[:, None, :, :2] + gt[:, None, :, 2:] / 2)
    inter = jnp.prod(jnp.clip(hi - lo, 0.0, None), axis=-1)
    union = (pred[..., 2] * pred[..., 3])[:, :, None] + \
        (gt[..., 2] * gt[..., 3])[:, None, :] - inter
    iou = jnp.where(gmask[:, None, :] > 0, inter / jnp.maximum(union, 1e-9),
                    0.0)
    ignore = (iou.max(axis=-1) > 0.5).astype(jnp.float32)
    neg = (1.0 - obj) * (1.0 - ignore)
    l_obj = jnp.sum(_bce(flat[..., 4], 1.0) * obj) + \
        jnp.sum(_bce(flat[..., 4], 0.0) * neg)
    onehot = jax.nn.one_hot(spec.class_index, spec.num_classes)
    l_cls = jnp.sum(_bce(flat[..., 5:], onehot).sum(-1) * obj)
    err = jnp.concatenate([sig - tbox[..., :2], t[..., 2:] - tbox[..., 2:]],
                          axis=-1)
    l_box = jnp.sum(jnp.sum(err ** 2, -1) * wt * obj)
    B = frames.shape[0]
    return (l_obj + l_cls + l_box) / B, stats


def train(arch: str, params, sample, steps: int, lr: float = 3e-3,
          log=None):
    """Adam on ``loss``; ``sample(step)`` -> (frames, boxes list) of one
    batch.  Running statistics follow each batch's with the spec's
    momentum.  -> (params, losses)."""
    from repro.optim import adamw
    spec = SPECS[arch]
    params = dict(params)
    obj = np.arange(len(spec.masks[0])) * (5 + spec.num_classes) + 4
    for k in range(len(spec.heads)):
        # objectness starts at the prior 0.01 (a negative anchor's
        # cross-entropy then starts small instead of swamping the
        # positives')
        p = dict(params[f"h{k}_out"])
        p["b"] = jnp.asarray(p["b"]).at[obj].set(float(np.log(0.01 / 0.99)))
        params[f"h{k}_out"] = p
    opt = adamw(lr=lr, weight_decay=0.0)
    state = opt.init(params)
    m = spec.bn_momentum

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, frames, obj, tbox, wt, gt, gmask, table):
        (lv, stats), g = jax.value_and_grad(loss, has_aux=True)(
            params, frames, obj, tbox, wt, gt, gmask, table, arch)
        params, state = opt.update(g, state, params)
        params = dict(params)
        for name, (mean, var) in stats.items():
            p = dict(params[name])
            p["mean"] = m * p["mean"] + (1 - m) * mean
            p["var"] = m * p["var"] + (1 - m) * var
            params[name] = p
        return params, state, lv

    losses = []
    for i in range(steps):
        frames, boxes = sample(i)
        H, W = frames.shape[1:3]
        targets = make_targets(boxes, H, W, arch)
        params, state, lv = step(params, state, jnp.asarray(frames),
                                 *(jnp.asarray(a) for a in targets),
                                 jnp.asarray(anchor_table(H, W, arch)))
        losses.append(float(lv))
        if log and len(losses) % 50 == 0:
            log(f"  step {len(losses)} loss {np.mean(losses[-50:]):.4f}")
    return params, losses
