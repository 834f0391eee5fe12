"""Recurrent reduced-rate tracker (§3.4).

Model = three components, per the paper:
  1. detection-level features: a small CNN over the detection's image crop,
     concatenated with the 4D box and the t_elapsed temporal feature
     (frames since the previous detection — what makes one model robust
     across every sampling gap g);
  2. track-level features: a GRU over the prefix's detection features
     (kept INCREMENTALLY at inference: one GRU step per appended
     detection, so reduced-rate tracking costs O(1) per track per frame);
  3. a matching MLP scoring (track feature, detection feature) pairs;
     Hungarian assignment on the score matrix, with a threshold below
     which a detection starts a new track.

Training (gap-randomized, §3.4): examples are sampled from θ_best tracks;
each example subsamples a track at a random gap g ~ G (one detection every
>= g frames), uses the last subsampled detection as the positive candidate
and same-frame detections of OTHER tracks as distractors, and trains the
pair score with BCE (calibrated probabilities -> the same threshold serves
Hungarian costs and new-track decisions).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.multiscope import TrackerConfig
from repro.core import fastmath as fm
from repro.core import hungarian as hg
from repro.core.hungarian import BIG, hungarian_device_np
from repro.models.common import ParamBuilder, build
from repro.obs.trace import NO_SPAN, TRACER
from repro.optim import adamw

BOX_FEATS = 6      # cx, cy, w, h, t_elapsed/8, log1p(t_elapsed)
REL_FEATS = 6      # dcx, dcy, dcx/te, dcy/te, dw, dh (candidate vs track)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def def_tracker(pb: ParamBuilder, cfg: TrackerConfig) -> None:
    C = cfg.crop
    e = cfg.embed_dim
    with pb.scope("crop_cnn"):
        pb.param("w0", (3, 3, 3, e // 2), (None,) * 4,
                 scale=1.0 / np.sqrt(27))
        pb.param("b0", (e // 2,), (None,), init="zeros")
        pb.param("w1", (3, 3, e // 2, e), (None,) * 4,
                 scale=1.0 / np.sqrt(9 * e // 2))
        pb.param("b1", (e,), (None,), init="zeros")
        flat = (C // 4) * (C // 4) * e
        pb.param("wd", (flat, e), (None, None))
        pb.param("bd", (e,), (None,), init="zeros")
    with pb.scope("det_proj"):
        pb.param("w", (e + BOX_FEATS, e), (None, None))
        pb.param("b", (e,), (None,), init="zeros")
    with pb.scope("gru"):
        h, f = cfg.rnn_dim, e
        pb.param("wz", (f + h, h), (None, None))
        pb.param("wr", (f + h, h), (None, None))
        pb.param("wh", (f + h, h), (None, None))
        pb.param("bz", (h,), (None,), init="zeros")
        pb.param("br", (h,), (None,), init="zeros")
        pb.param("bh", (h,), (None,), init="zeros")
    with pb.scope("match"):
        pb.param("w0", (cfg.rnn_dim + e + REL_FEATS, cfg.match_hidden),
                 (None, None))
        pb.param("b0", (cfg.match_hidden,), (None,), init="zeros")
        pb.param("w1", (cfg.match_hidden, 1), (None, None))
        pb.param("b1", (1,), (None,), init="zeros")


def init_tracker(cfg: TrackerConfig, seed: int = 0):
    return build(functools.partial(def_tracker, cfg=cfg), "init",
                 seed=seed)


# ---------------------------------------------------------------------------
# Forward pieces (fixed-shape jit)
# ---------------------------------------------------------------------------

def _conv(x, w, b, stride):
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + b)


@jax.jit
def crop_embed(params, crops):
    """crops: (N, C, C, 3) -> (N, e) crop-CNN features.

    The te-INDEPENDENT part of the detection embedding: inference
    computes it once per detection (batched per chunk by the engine) and
    derives every te-dependent embedding from it host-side."""
    p = params["crop_cnn"]
    x = _conv(crops, p["w0"], p["b0"], 2)
    x = _conv(x, p["w1"], p["b1"], 2)
    x = x.reshape(x.shape[0], -1)
    # repro-lint: disable=bit-contract -- crop CNN runs upstream of the host/device split: one impl, both paths consume its output
    return jnp.tanh(x @ p["wd"] + p["bd"])


@jax.jit
def embed_dets(params, crops, boxes, t_elapsed):
    """crops: (N, C, C, 3); boxes: (N, 4); t_elapsed: (N,) -> (N, e)."""
    x = crop_embed(params, crops)
    te = t_elapsed.astype(jnp.float32)
    extra = jnp.stack([boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3],
                       te / 8.0, jnp.log1p(te)], axis=1)
    d = jnp.concatenate([x, extra], axis=1)
    dp = params["det_proj"]
    # repro-lint: disable=bit-contract -- train-only head; inference twins are _det_feats_np (host) / kernels.track_step (device)
    return jnp.tanh(d @ dp["w"] + dp["b"])


@jax.jit
def gru_step(params, h, feat):
    """h: (..., H); feat: (..., e) -> new h."""
    g = params["gru"]
    hf = jnp.concatenate([feat, h], axis=-1)
    # repro-lint: disable=bit-contract -- train-only head; inference twins are _gru_np (host) / kernels.track_step (device)
    z = jax.nn.sigmoid(hf @ g["wz"] + g["bz"])
    # repro-lint: disable=bit-contract -- train-only head; inference twins are _gru_np (host) / kernels.track_step (device)
    r = jax.nn.sigmoid(hf @ g["wr"] + g["br"])
    hf2 = jnp.concatenate([feat, r * h], axis=-1)
    # repro-lint: disable=bit-contract -- train-only head; inference twins are _gru_np (host) / kernels.track_step (device)
    cand = jnp.tanh(hf2 @ g["wh"] + g["bh"])
    return (1 - z) * h + z * cand


def _rel_features(track_boxes, det_boxes, te):
    """track_boxes: (T, 4); det_boxes: (N, 4); te: (N,) -> (T, N, 6)."""
    d = det_boxes[None, :, :] - track_boxes[:, None, :]      # (T, N, 4)
    tesafe = jnp.maximum(te, 1.0)[None, :, None]
    return jnp.concatenate([
        d[..., :2], d[..., :2] / tesafe, d[..., 2:]], axis=-1)


@jax.jit
def match_logits(params, track_h, track_boxes, det_feats, det_boxes, te):
    """track_h: (T, H); track_boxes: (T, 4) last box per track;
    det_feats: (N, e); det_boxes: (N, 4); te: (N,) -> (T, N) logits."""
    m = params["match"]
    T, N = track_h.shape[0], det_feats.shape[0]
    rel = _rel_features(track_boxes, det_boxes, te)
    pair = jnp.concatenate([
        jnp.broadcast_to(track_h[:, None], (T, N, track_h.shape[1])),
        jnp.broadcast_to(det_feats[None], (T, N, det_feats.shape[1])),
        rel,
    ], axis=-1)
    # repro-lint: disable=bit-contract -- train-only head; inference twins are _match_np (host) / kernels.track_step (device)
    hid = jnp.tanh(pair @ m["w0"] + m["b0"])
    # repro-lint: disable=bit-contract -- train-only head; inference twins are _match_np (host) / kernels.track_step (device)
    return (hid @ m["w1"] + m["b1"])[..., 0]


@jax.jit
def _train_loss(params, crops, boxes, te, prefix_mask, cand_mask, labels,
                last_box):
    """One batch of listwise examples.

    crops/boxes/te: (B, L + K, C, C, 3)/(B, L+K, 4)/(B, L+K) — first L
    slots are the prefix detections (masked by prefix_mask (B, L)), the
    remaining K are candidates (masked by cand_mask (B, K));
    labels: (B, K) {0,1} (the true continuation has 1).
    """
    B, LK = boxes.shape[:2]
    feats = embed_dets(params, crops.reshape(B * LK, *crops.shape[2:]),
                       boxes.reshape(B * LK, 4), te.reshape(B * LK))
    feats = feats.reshape(B, LK, -1)
    L = prefix_mask.shape[1]
    K = cand_mask.shape[1]
    pre, cand = feats[:, :L], feats[:, L:]
    H = params["gru"]["bz"].shape[0]

    def scan_body(h, x):
        f, m = x
        h2 = gru_step(params, h, f)
        h = jnp.where(m[:, None] > 0, h2, h)
        return h, None

    h0 = jnp.zeros((B, H), jnp.float32)
    hT, _ = jax.lax.scan(scan_body, h0,
                         (jnp.moveaxis(pre, 1, 0),
                          jnp.moveaxis(prefix_mask, 1, 0)))
    # score each candidate against its own example's track feature,
    # with relative-motion features vs the prefix's LAST box
    m = params["match"]
    cboxes = boxes[:, L:]                               # (B, K, 4)
    cte = jnp.maximum(te[:, L:], 1.0)[..., None]
    d = cboxes - last_box[:, None, :]
    rel = jnp.concatenate([d[..., :2], d[..., :2] / cte, d[..., 2:]],
                          axis=-1)
    pair = jnp.concatenate(
        [jnp.broadcast_to(hT[:, None], (B, K, H)), cand, rel], axis=-1)
    # repro-lint: disable=bit-contract -- training loss; never on the serving path
    hid = jnp.tanh(pair @ m["w0"] + m["b0"])
    # repro-lint: disable=bit-contract -- training loss; never on the serving path
    logits = (hid @ m["w1"] + m["b1"])[..., 0]          # (B, K)
    y = labels.astype(jnp.float32)
    bce = jnp.maximum(logits, 0) - logits * y \
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))  # repro-lint: disable=bit-contract -- training loss; never on the serving path
    return (bce * cand_mask).sum() / jnp.maximum(cand_mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Crop extraction (host)
# ---------------------------------------------------------------------------

def extract_crop(frame: np.ndarray, box: np.ndarray, crop: int
                 ) -> np.ndarray:
    """Nearest-neighbor resample of the box region to (crop, crop, 3)."""
    return extract_crops(frame, np.asarray(box)[None], crop)[0]


def extract_crops(frame: np.ndarray, boxes: np.ndarray, crop: int
                  ) -> np.ndarray:
    """Batched ``extract_crop``: (n, >=4) boxes -> (n, crop, crop, 3),
    one vectorized gather per frame instead of one per detection."""
    H, W = frame.shape[:2]
    n = len(boxes)
    if n == 0:
        return np.zeros((0, crop, crop, 3), frame.dtype)
    b = np.asarray(boxes)[:, :4]
    x0, x1 = (b[:, 0] - b[:, 2] / 2) * W, (b[:, 0] + b[:, 2] / 2) * W
    y0, y1 = (b[:, 1] - b[:, 3] / 2) * H, (b[:, 1] + b[:, 3] / 2) * H
    xs = np.clip(np.linspace(x0, x1, crop, axis=1).astype(np.int64),
                 0, W - 1)
    ys = np.clip(np.linspace(y0, y1, crop, axis=1).astype(np.int64),
                 0, H - 1)
    return frame[ys[:, :, None], xs[:, None, :]]


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

@dataclass
class TrackExample:
    """One θ_best track on one clip, with crops pre-extracted."""
    frames: np.ndarray           # (n,)
    boxes: np.ndarray            # (n, 4)
    crops: np.ndarray            # (n, C, C, 3)
    clip_key: int = 0            # same-clip grouping for hard negatives


def build_examples(tracks: Sequence[np.ndarray],
                   frame_getter, crop: int,
                   clip_key: int = 0) -> List[TrackExample]:
    """tracks: (n, 6) [frame, cx, cy, w, h, id] arrays; frame_getter(f)
    -> rendered frame."""
    out = []
    for tr in tracks:
        if len(tr) < 3:
            continue
        crops = np.stack([
            extract_crop(frame_getter(int(f)), b, crop)
            for f, b in zip(tr[:, 0], tr[:, 1:5])])
        out.append(TrackExample(tr[:, 0].astype(np.int64), tr[:, 1:5],
                                crops, clip_key))
    return out


def train_tracker(cfg: TrackerConfig, examples: List[TrackExample],
                  steps: int = 1500, batch: int = 32, seed: int = 0,
                  lr: float = 3e-3, max_prefix: int = 6, n_cand: int = 6):
    params = init_tracker(cfg, seed)
    opt = adamw(lr=lr, weight_decay=0.0)
    state = opt.init(params)
    vg = jax.jit(jax.value_and_grad(_train_loss))
    rng = np.random.default_rng(seed)
    C = cfg.crop
    gaps = cfg.gaps
    losses = []
    if not examples:
        return params, losses

    def sample_example():
        ex = examples[rng.integers(len(examples))]
        g = int(gaps[rng.integers(len(gaps))])
        # subsample at gap g: next det >= g frames after the previous
        idx = [0]
        for i in range(1, len(ex.frames)):
            if ex.frames[i] - ex.frames[idx[-1]] >= g:
                idx.append(i)
        if len(idx) < 2:
            return None
        split = int(rng.integers(1, len(idx)))
        prefix, pos = idx[:split], idx[split]
        prefix = prefix[-max_prefix:]
        pos_frame = int(ex.frames[pos])
        # distractors: same-frame detections of other tracks; SAME-CLIP
        # tracks preferred (hard negatives — nearby objects in the same
        # scene) with random-clip fallback
        negs = []
        same = [o for o in examples
                if o is not ex and o.clip_key == ex.clip_key]
        pools = (same, examples)
        for pool in pools:
            for _ in range(3 * (n_cand - 1)):
                if len(negs) >= n_cand - 1 or not pool:
                    break
                other = pool[rng.integers(len(pool))]
                if other is ex:
                    continue
                j = np.searchsorted(other.frames, pos_frame)
                j = min(j, len(other.frames) - 1)
                # same-clip negatives must actually overlap in time
                if pool is same and abs(int(other.frames[j])
                                        - pos_frame) > 8:
                    continue
                negs.append((other, j))
            if len(negs) >= n_cand - 1:
                break
        return ex, prefix, pos, negs

    L, K = max_prefix, n_cand
    for step in range(steps):
        crops = np.zeros((batch, L + K, C, C, 3), np.float32)
        boxes = np.zeros((batch, L + K, 4), np.float32)
        te = np.zeros((batch, L + K), np.float32)
        pmask = np.zeros((batch, L), np.float32)
        cmask = np.zeros((batch, K), np.float32)
        labels = np.zeros((batch, K), np.float32)
        last_box = np.zeros((batch, 4), np.float32)
        b = 0
        while b < batch:
            s = sample_example()
            if s is None:
                continue
            ex, prefix, pos, negs = s
            off = L - len(prefix)
            prev_f = None
            for slot, i in enumerate(prefix):
                crops[b, off + slot] = ex.crops[i]
                boxes[b, off + slot] = ex.boxes[i]
                te[b, off + slot] = 0 if prev_f is None else \
                    ex.frames[i] - prev_f
                pmask[b, off + slot] = 1
                prev_f = ex.frames[i]
            last_box[b] = ex.boxes[prefix[-1]]
            t_gap = float(ex.frames[pos] - ex.frames[prefix[-1]])
            crops[b, L] = ex.crops[pos]
            boxes[b, L] = ex.boxes[pos]
            te[b, L] = t_gap
            cmask[b, 0] = 1
            labels[b, 0] = 1
            for slot, (other, j) in enumerate(negs):
                crops[b, L + 1 + slot] = other.crops[j]
                boxes[b, L + 1 + slot] = other.boxes[j]
                te[b, L + 1 + slot] = t_gap
                cmask[b, 1 + slot] = 1
            b += 1
        loss, g = vg(params, jnp.asarray(crops), jnp.asarray(boxes),
                     jnp.asarray(te), jnp.asarray(pmask),
                     jnp.asarray(cmask), jnp.asarray(labels),
                     jnp.asarray(last_box))
        params, state = opt.update(g, state, params)
        losses.append(float(loss))
    return params, losses


# ---------------------------------------------------------------------------
# Inference
# ---------------------------------------------------------------------------

@dataclass
class _ActiveTrack:
    track_id: int
    h: np.ndarray                # GRU state
    frames: List[int]
    boxes: List[np.ndarray]
    misses: int = 0

    def as_array(self) -> np.ndarray:
        out = np.zeros((len(self.frames), 6), np.float32)
        out[:, 0] = self.frames
        out[:, 1:5] = np.stack(self.boxes)
        out[:, 5] = self.track_id
        return out


def _pad(n: int, mult: int = 8) -> int:
    return max(mult, ((n + mult - 1) // mult) * mult)


def _host_params(params) -> Dict[str, np.ndarray]:
    """One-time numpy copies of the SMALL heads (det_proj, gru, match).

    Inference runs these host-side: per-frame work is a handful of tiny
    matmuls on <= max_tracks rows, where jit dispatch + device_put costs
    orders of magnitude more than the math.  The crop CNN (the only real
    compute) stays on the accelerator via ``crop_embed``."""
    out = {}
    for scope in ("det_proj", "gru", "match"):
        for k, v in params[scope].items():
            out[f"{scope}/{k}"] = np.asarray(v)
    return out


class RecurrentTracker:
    """Online inference: incremental GRU states + Hungarian matching.

    Split execution: the crop CNN (``crop_embed``) runs batched on the
    accelerator — once per chunk under the chunked engine, once per frame
    on the reference path — while the te-dependent projection, GRU steps
    and the matching MLP run host-side in numpy (same host/accelerator
    split as Hungarian itself).  Both engines call the same code, so
    their tracks are bit-identical.

    Every host head routes through ``repro.core.fastmath``'s ``np_*``
    flavors and association through ``hungarian_device_np`` (the f32 JV
    twin of the Pallas solver), which makes the host step BIT-IDENTICAL
    to the fused device step (``kernels.track_step``): with
    ``assign="device"`` the whole per-frame step — detection features,
    match logits, cost assembly, JV assignment and both GRU batches —
    runs as ONE kernel dispatch and the host merely replays the
    returned events onto its track objects.  ``DeviceTracker`` extends
    that to one dispatch per CHUNK.
    """

    def __init__(self, cfg: TrackerConfig, params, max_misses: int = 2,
                 min_hits: int = 2, assign: str = "host"):
        assert assign in ("host", "device")
        self.cfg = cfg
        self.params = params
        self.np_params = _host_params(params)
        self.max_misses = max_misses
        self.min_hits = min_hits
        self.assign = assign
        self.active: List[_ActiveTrack] = []
        self.finished: List[_ActiveTrack] = []
        self._next_id = 0
        self._last_frame: Optional[int] = None
        # device-step operands (lazy: host-only trackers never pack)
        self._packed = None
        self._thr = np.full((1, 1), cfg.match_threshold, np.float32)
        # cross-stream TrackBroker handle, attached by the executor
        self._track_handle = None
        # device dispatches issued by this tracker (crop CNN per-frame
        # fallback + track-step kernels); read by the TRACK stage timer
        self.dispatches = 0
        # host-twin work done by ``step``: JV column-scan steps and
        # elements ``np_fmadd`` routed through its exact tie path;
        # read as deltas by the ``track.assoc`` span
        self.jv_steps = 0
        self.fma_ties = 0

    def _device_operands(self):
        if self._packed is None:
            from repro.kernels.track_step import pack_params
            from repro.kernels.track_step.ops import LOG1P_TABLE_2D
            self._packed = (pack_params(self.np_params), LOG1P_TABLE_2D)
        return self._packed

    # -- host-side heads (numpy twins of the ``kernels.track_step``
    #    pieces, minus the crop CNN; every transcendental/multiply-add
    #    routes through fastmath so host == device bit-for-bit) ----------

    def _det_feats_np(self, x: np.ndarray, boxes: np.ndarray,
                      te: np.ndarray) -> np.ndarray:
        """x: (N, e) crop embeddings -> (N, e) detection features."""
        p = self.np_params
        te = np.asarray(te, np.float32)
        extra = np.stack([boxes[:, 0], boxes[:, 1], boxes[:, 2],
                          boxes[:, 3], te * np.float32(0.125),
                          fm.np_log1p_int(te)],
                         axis=1).astype(np.float32)
        d = np.concatenate([x, extra], axis=1)
        return fm.np_tanh(fm.np_matmul(d, p["det_proj/w"])
                          + p["det_proj/b"])

    def _gru_np(self, h: np.ndarray, feat: np.ndarray) -> np.ndarray:
        p = self.np_params
        hf = np.concatenate([feat, h], axis=-1)
        z = fm.np_sigmoid(fm.np_matmul(hf, p["gru/wz"]) + p["gru/bz"])
        r = fm.np_sigmoid(fm.np_matmul(hf, p["gru/wr"]) + p["gru/br"])
        hf2 = np.concatenate([feat, r * h], axis=-1)
        cand = fm.np_tanh(fm.np_matmul(hf2, p["gru/wh"]) + p["gru/bh"])
        # single-multiply blend == the kernel's h + z*(cand - h)
        return fm.np_fmadd(z, cand - h, h)

    def _match_np(self, hs: np.ndarray, tboxes: np.ndarray,
                  feats: np.ndarray, dboxes: np.ndarray,
                  te: np.ndarray) -> np.ndarray:
        p = self.np_params
        T, N = hs.shape[0], feats.shape[0]
        d = dboxes[None, :, :] - tboxes[:, None, :]
        tesafe = np.maximum(te, np.float32(1.0))[None, :, None]
        rel = np.concatenate([d[..., :2], d[..., :2] / tesafe,
                              d[..., 2:]], axis=-1)
        pair = np.concatenate([
            np.broadcast_to(hs[:, None], (T, N, hs.shape[1])),
            np.broadcast_to(feats[None], (T, N, feats.shape[1])),
            rel,
        ], axis=-1)
        hid = fm.np_tanh(fm.np_matmul(pair.reshape(T * N, -1),
                                      p["match/w0"]) + p["match/b0"])
        return (fm.np_matmul(hid, p["match/w1"])
                + p["match/b1"]).reshape(T, N)

    def step(self, frame_idx: int, dets: np.ndarray,
             frame: np.ndarray,
             det_embeds: Optional[np.ndarray] = None) -> None:
        """dets: (n, >=4) world-unit detections; frame: rendered pixels.

        det_embeds: optional precomputed (n, embed_dim) CROP embeddings
        (``crop_embed`` outputs — one accelerator dispatch per CHUNK
        instead of per frame); te-dependent features are derived from
        them host-side, so the same embeddings serve both the matching
        candidates and the GRU updates."""
        cfg = self.cfg
        n = len(dets)
        jv0, ties0 = hg.COUNTS.jv_steps, fm.COUNTS.fma_ties
        te_scalar = 0.0 if self._last_frame is None else \
            float(frame_idx - self._last_frame)
        self._last_frame = frame_idx
        C = cfg.crop
        if det_embeds is not None:
            x = det_embeds
        elif n > 0:
            crops = extract_crops(frame, dets, C)
            npad = _pad(n)
            crops_p = np.zeros((npad, C, C, 3), np.float32)
            crops_p[:n] = crops
            self.dispatches += 1
            x = np.asarray(crop_embed(self.params,
                                      jnp.asarray(crops_p)))[:n]
        else:
            x = np.zeros((0, cfg.embed_dim), np.float32)
        boxes = dets[:, :4].astype(np.float32) if n > 0 else \
            np.zeros((0, 4), np.float32)

        T = len(self.active)
        use_dev = self.assign == "device" and n > 0
        h_upd = h_new = None
        if use_dev:
            pairs, h_upd, h_new = self._device_step(
                frame_idx, te_scalar, x, boxes)
        else:
            pairs = []
            if T > 0 and n > 0:
                feats = self._det_feats_np(
                    x, boxes, np.full((n,), te_scalar, np.float32))
                hs = np.stack([t.h for t in self.active])
                tboxes = np.stack([t.boxes[-1] for t in self.active])
                te_arr = np.full((n,), max(te_scalar, 1.0), np.float32)
                logits = self._match_np(hs, tboxes, feats, boxes,
                                        te_arr)
                probs = fm.np_sigmoid(logits)
                cost = np.where(
                    probs >= np.float32(cfg.match_threshold),
                    np.float32(1.0) - probs, np.float32(BIG))
                pairs = hungarian_device_np(cost)

        matched_t, matched_d = set(), set()
        upd_feats, upd_tracks = [], []
        for ti, di in pairs:
            t = self.active[ti]
            # GRU update uses the WITHIN-TRACK gap
            gap = float(frame_idx - t.frames[-1])
            upd_tracks.append(t)
            upd_feats.append((di, gap))
            if use_dev:
                t.h = np.asarray(h_upd[ti], np.float32)
            t.frames.append(frame_idx)
            t.boxes.append(dets[di, :4].astype(np.float32))
            t.misses = 0
            matched_t.add(ti)
            matched_d.add(di)
        # age out unmatched
        survivors = []
        for ti, t in enumerate(self.active):
            if ti in matched_t:
                survivors.append(t)
                continue
            t.misses += 1
            if t.misses > self.max_misses:
                self.finished.append(t)
            else:
                survivors.append(t)
        self.active = survivors

        # GRU advance: matched-track updates (t_elapsed = within-track
        # gap, h = track state) and new-track starts (t_elapsed = 0,
        # h = 0) reuse the crop embeddings — no second CNN pass.  On
        # the device path both GRU batches already ran inside the
        # fused kernel; the loop merely scatters the returned rows.
        new_idx = [di for di in range(n) if di not in matched_d]
        n_upd = len(upd_tracks)
        m = n_upd + len(new_idx)
        if m > 0:
            if use_dev:
                for di in new_idx:
                    t = _ActiveTrack(self._next_id,
                                     np.asarray(h_new[di], np.float32),
                                     [frame_idx],
                                     [dets[di, :4].astype(np.float32)])
                    self.active.append(t)
                    self._next_id += 1
            else:
                rows = [di for di, _ in upd_feats] + new_idx
                te_u = np.asarray([g for _, g in upd_feats]
                                  + [0.0] * len(new_idx), np.float32)
                hs_p = np.zeros((m, self.cfg.rnn_dim), np.float32)
                for k, t in enumerate(upd_tracks):
                    hs_p[k] = t.h
                f_u = self._det_feats_np(x[rows], boxes[rows], te_u)
                h_out = self._gru_np(hs_p, f_u)
                for k, t in enumerate(upd_tracks):
                    t.h = h_out[k]
                for k, di in enumerate(new_idx):
                    t = _ActiveTrack(self._next_id, h_out[n_upd + k],
                                     [frame_idx],
                                     [dets[di, :4].astype(np.float32)])
                    self.active.append(t)
                    self._next_id += 1
        # cap active set (static max_tracks capacity)
        if len(self.active) > self.cfg.max_tracks:
            self.active.sort(key=lambda t: -len(t.frames))
            self.finished.extend(self.active[self.cfg.max_tracks:])
            self.active = self.active[:self.cfg.max_tracks]
        self.jv_steps += hg.COUNTS.jv_steps - jv0
        self.fma_ties += fm.COUNTS.fma_ties - ties0

    def _device_step(self, frame_idx: int, te_scalar: float,
                     x: np.ndarray, boxes: np.ndarray):
        """One whole tracker step as ONE fused kernel dispatch.

        Packs the active set and the frame's detections into the
        kernel's pow2 slot square (live tracks as the row prefix in
        active-list order, detections as the column prefix), runs
        ``kernels.track_step`` — or submits to the cross-stream
        ``TrackBroker`` when one is attached — and returns (pairs,
        h_upd rows per track row, h_new rows per det column).  Bit-
        identical to the host twins at ANY slot count: the kernel
        restricts its JV solve to the canonical ``assoc_side`` square
        the host solves (f32 JV is not padding-invariant)."""
        from repro.core.detector import next_bucket

        T, n = len(self.active), len(boxes)
        e = self.cfg.embed_dim
        H = self.cfg.rnn_dim
        Q = next_bucket(max(T, n, 1), min_bucket=8)
        h_r = np.zeros((Q, H), np.float32)
        tbox_r = np.zeros((Q, 4), np.float32)
        alive_r = np.zeros((Q,), np.float32)
        te_gap_r = np.zeros((Q,), np.float32)
        for ti, t in enumerate(self.active):
            h_r[ti] = t.h
            tbox_r[ti] = t.boxes[-1]
            alive_r[ti] = 1.0
            te_gap_r[ti] = frame_idx - t.frames[-1]
        te_match = np.full((Q,), te_scalar, np.float32)
        x_p = np.zeros((Q, e), np.float32)
        x_p[:n] = x
        dbox = np.zeros((Q, 4), np.float32)
        dbox[:n] = boxes
        dvalid = np.zeros((Q,), np.float32)
        dvalid[:n] = 1.0
        params, table = self._device_operands()
        self.dispatches += 1
        if self._track_handle is not None:
            matched, h_upd, h_new = self._track_handle.step(
                h_r, tbox_r, alive_r, te_gap_r, te_match, x_p, dbox,
                dvalid, self._thr, params, table,
                params_key=id(self.params))
        else:
            from repro.kernels.track_step import track_step
            out = track_step(h_r[None], tbox_r[None], alive_r[None],
                             te_gap_r[None], te_match[None], x_p[None],
                             dbox[None], dvalid[None], self._thr,
                             params, table)
            matched, h_upd, h_new = (np.asarray(o[0]) for o in out)
        pairs = [(ti, int(matched[ti])) for ti in range(T)
                 if matched[ti] >= 0]
        return pairs, h_upd, h_new

    def step_chunk(self, frame_ids: Sequence[int],
                   dets_per_frame: Sequence[np.ndarray],
                   frames: Sequence[np.ndarray],
                   embeds: Optional[Sequence[np.ndarray]] = None
                   ) -> None:
        """Feed one chunk in frame order.  The base tracker simply
        loops ``step`` (host math, or one kernel dispatch per frame
        with ``assign="device"``); ``DeviceTracker`` overrides this
        with a single chunk-scan dispatch."""
        for k, f in enumerate(frame_ids):
            self.step(int(f), dets_per_frame[k], frames[k],
                      det_embeds=None if embeds is None else embeds[k])

    def result(self) -> List[np.ndarray]:
        tracks = self.finished + self.active
        return [t.as_array() for t in tracks
                if len(t.frames) >= self.min_hits]


# sorting key for dead slots: past any live track's recency rank
_BIGK = np.int32(1 << 30)


@functools.partial(jax.jit, static_argnames=("max_misses", "max_tracks"))
def _device_chunk_scan(carry, fidx, x, dbox, dvalid, thr, params, table,
                       *, max_misses: int, max_tracks: int):
    """Whole-chunk tracker recurrence: ``lax.scan`` over B frames, one
    fused ``kernels.track_step`` call per step, entirely on device.

    carry (slot space, Q slots): h (Q, H), tbox (Q, 4), alive (Q,) f32,
    last_f/misses/length/order (Q,) i32, next_key i32 (the next
    active-list rank to issue), last_g i32 (previously processed frame,
    -1 for none).  Inputs: fidx (B,) i32; x (B, Q, e); dbox (B, Q, 4);
    dvalid (B, Q) with each frame's detections as a column prefix.

    ``order`` encodes the host tracker's active-LIST position (matched
    tracks keep their rank, new tracks append, a max_tracks overflow
    re-sorts by track length); each step gathers slots into rank order,
    so the kernel sees exactly the rows the per-frame path would build
    and every step stays bit-identical to ``RecurrentTracker.step``.

    Returns per-frame events for the host replay: matched det column
    per slot (or -1), assigned slot per det column (Q for none), and
    the post-step h per slot."""
    from repro.kernels.track_step import track_step

    Q = carry[0].shape[0]
    slot = jnp.arange(Q, dtype=jnp.int32)

    def body(c, inp):
        h, tbox, alive, last_f, misses, length, order, next_key, \
            last_g = c
        f, xk, dbk, dvk = inp
        live = alive > 0
        te_m = jnp.where(last_g < 0, 0, f - last_g).astype(jnp.float32)
        perm = jnp.argsort(jnp.where(live, order, _BIGK + slot))
        alive_r = alive[perm]
        te_gap_r = jnp.where(alive_r > 0,
                             (f - last_f[perm]).astype(jnp.float32),
                             np.float32(0))
        matched_r, h_upd_r, h_new = (o[0] for o in track_step(
            h[perm][None], tbox[perm][None], alive_r[None],
            te_gap_r[None], jnp.full((Q,), te_m)[None], xk[None],
            dbk[None], dvk[None], thr, params, table))
        # back to slot space; apply matched-track updates
        m_slot = jnp.full((Q,), -1, jnp.int32).at[perm].set(matched_r)
        is_m = m_slot >= 0
        mcol = jnp.clip(m_slot, 0, Q - 1)
        h = jnp.where(is_m[:, None],
                      jnp.zeros_like(h).at[perm].set(h_upd_r), h)
        tbox = jnp.where(is_m[:, None], dbk[mcol], tbox)
        last_f = jnp.where(is_m, f, last_f)
        length = jnp.where(is_m, length + 1, length)
        misses = jnp.where(is_m, 0, misses)
        # age out unmatched live tracks
        aged = live & ~is_m
        misses = jnp.where(aged, misses + 1, misses)
        alive = jnp.where(aged & (misses > max_misses),
                          np.float32(0), alive)
        # unmatched detections start new tracks in ascending free slots,
        # ranks appended after every existing track (host list append)
        det_hit = jnp.zeros((Q + 1,), jnp.int32).at[
            jnp.where(matched_r >= 0, matched_r, Q)].set(1)[:Q]
        new_mask = (dvk > 0) & (det_hit == 0)
        free = alive <= 0
        free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        slot_for_rank = jnp.full((Q,), Q, jnp.int32).at[
            jnp.where(free, free_rank, Q)].set(slot, mode="drop")
        new_rank = jnp.cumsum(new_mask.astype(jnp.int32)) - 1
        tgt = jnp.where(new_mask,
                        slot_for_rank[jnp.clip(new_rank, 0, Q - 1)], Q)
        alive = alive.at[tgt].set(1.0, mode="drop")
        h = h.at[tgt].set(h_new, mode="drop")
        tbox = tbox.at[tgt].set(dbk, mode="drop")
        last_f = last_f.at[tgt].set(f, mode="drop")
        misses = misses.at[tgt].set(0, mode="drop")
        length = length.at[tgt].set(1, mode="drop")
        order = order.at[tgt].set(next_key + new_rank, mode="drop")
        next_key = next_key + new_mask.astype(jnp.int32).sum()
        # capacity overflow: keep the max_tracks longest tracks (stable
        # on list order — the host's in-place sort) and renumber ranks
        n_alive = (alive > 0).astype(jnp.int32).sum()
        over = n_alive > max_tracks
        perm2 = jnp.lexsort((jnp.where(alive > 0, order, _BIGK + slot),
                             jnp.where(alive > 0, -length, _BIGK)))
        pos = jnp.zeros((Q,), jnp.int32).at[perm2].set(slot)
        alive = jnp.where(over & (alive > 0) & (pos >= max_tracks),
                          np.float32(0), alive)
        order = jnp.where(over, pos, order)
        next_key = jnp.where(over, max_tracks, next_key)
        return ((h, tbox, alive, last_f, misses, length, order,
                 next_key, f), (m_slot, tgt, h))

    _, ys = jax.lax.scan(body, carry, (fidx, x, dbox, dvalid))
    return ys


class DeviceTracker(RecurrentTracker):
    """Chunk-scan tracker: ONE device dispatch per chunk.

    Same tracks, bit for bit, as ``RecurrentTracker`` — the fused step
    kernel shares its math with the host twins via ``fastmath`` — but
    the per-frame recurrence runs as a ``lax.scan`` over the chunk with
    track state held in a padded slot buffer on device, so B frames
    cost one dispatch instead of B host round trips.  The host
    materializes track objects once per chunk by replaying the scan's
    (matched, new-slot, h) event stream.

    With a cross-stream ``TrackBroker`` handle attached the per-frame
    fused step is used instead (the broker batches steps ACROSS
    streams, which a per-stream scan cannot), so the live per-frame
    regime still shares dispatches."""

    def __init__(self, cfg: TrackerConfig, params, max_misses: int = 2,
                 min_hits: int = 2, assign: str = "device"):
        super().__init__(cfg, params, max_misses=max_misses,
                         min_hits=min_hits, assign="device")

    def step_chunk(self, frame_ids: Sequence[int],
                   dets_per_frame: Sequence[np.ndarray],
                   frames: Sequence[np.ndarray],
                   embeds: Optional[Sequence[np.ndarray]] = None
                   ) -> None:
        B = len(frame_ids)
        if B == 0:
            return
        if self._track_handle is not None:
            super().step_chunk(frame_ids, dets_per_frame, frames,
                               embeds)
            return
        cfg = self.cfg
        if embeds is None:
            self.dispatches += 1
            embeds = embed_dets_chunk(self.params, cfg, frames,
                                      dets_per_frame)
        from repro.core.detector import next_bucket
        T = len(self.active)
        D = max((len(d) for d in dets_per_frame), default=0)
        Q = next_bucket(max(T, cfg.max_tracks) + D, min_bucket=8)
        H, e = cfg.rnn_dim, cfg.embed_dim
        h0 = np.zeros((Q, H), np.float32)
        tbox0 = np.zeros((Q, 4), np.float32)
        alive0 = np.zeros((Q,), np.float32)
        lastf0 = np.zeros((Q,), np.int32)
        miss0 = np.zeros((Q,), np.int32)
        len0 = np.zeros((Q,), np.int32)
        order0 = np.zeros((Q,), np.int32)
        for i, t in enumerate(self.active):
            h0[i] = t.h
            tbox0[i] = t.boxes[-1]
            alive0[i] = 1.0
            lastf0[i] = t.frames[-1]
            miss0[i] = t.misses
            len0[i] = len(t.frames)
            order0[i] = i
        last_g0 = np.int32(-1 if self._last_frame is None
                           else self._last_frame)
        fidx = np.asarray([int(f) for f in frame_ids], np.int32)
        x = np.zeros((B, Q, e), np.float32)
        dbox = np.zeros((B, Q, 4), np.float32)
        dvalid = np.zeros((B, Q), np.float32)
        for k in range(B):
            n = len(dets_per_frame[k])
            if n:
                x[k, :n] = embeds[k]
                dbox[k, :n] = np.asarray(
                    dets_per_frame[k], np.float32)[:, :4]
                dvalid[k, :n] = 1.0
        params, table = self._device_operands()
        self.dispatches += 1
        m_ev, new_ev, h_ev = _device_chunk_scan(
            (h0, tbox0, alive0, lastf0, miss0, len0, order0,
             np.int32(T), last_g0),
            fidx, x, dbox, dvalid, self._thr, params, table,
            max_misses=self.max_misses, max_tracks=cfg.max_tracks)
        m_ev = np.asarray(m_ev)
        new_ev = np.asarray(new_ev)
        h_ev = np.asarray(h_ev)

        # replay the event stream onto host track objects; ``slots``
        # stays parallel to ``self.active``
        slots = list(range(T))
        for k in range(B):
            f = int(frame_ids[k])
            dets = dets_per_frame[k]
            ms, hs = m_ev[k], h_ev[k]
            keep_t: List[_ActiveTrack] = []
            keep_s: List[int] = []
            for t, s in zip(self.active, slots):
                di = int(ms[s])
                if di >= 0:
                    t.h = hs[s].copy()
                    t.frames.append(f)
                    t.boxes.append(dets[di, :4].astype(np.float32))
                    t.misses = 0
                    keep_t.append(t)
                    keep_s.append(s)
                else:
                    t.misses += 1
                    if t.misses > self.max_misses:
                        self.finished.append(t)
                    else:
                        keep_t.append(t)
                        keep_s.append(s)
            self.active, slots = keep_t, keep_s
            for di in range(len(dets)):
                s = int(new_ev[k][di])
                if s < Q:
                    t = _ActiveTrack(self._next_id, hs[s].copy(), [f],
                                     [dets[di, :4].astype(np.float32)])
                    self.active.append(t)
                    slots.append(s)
                    self._next_id += 1
            if len(self.active) > cfg.max_tracks:
                ranked = sorted(zip(self.active, slots),
                                key=lambda ts: -len(ts[0].frames))
                self.finished.extend(
                    t for t, _ in ranked[cfg.max_tracks:])
                self.active = [t for t, _ in ranked[:cfg.max_tracks]]
                slots = [s for _, s in ranked[:cfg.max_tracks]]
            self._last_frame = f


def embed_dets_chunk(params, cfg: TrackerConfig,
                     frames: Sequence[np.ndarray],
                     dets_per_frame: Sequence[np.ndarray],
                     min_bucket: int = 8) -> List[np.ndarray]:
    """Run the crop CNN over every detection in a CHUNK in one
    bucket-padded ``crop_embed`` dispatch (the executor's TRACK-stage
    batching).  Returns per-frame (n_i, embed_dim) crop embeddings,
    bit-identical to per-frame ``RecurrentTracker.step`` computation
    (conv outputs are per-sample independent of batch padding).

    ``min_bucket`` is the bucket floor; the executor scales it with the
    chunk size B so the set of distinct power-of-two buckets — and with
    it the number of ``crop_embed`` jit specializations — stays bounded
    as the tuner proposes larger chunks."""
    C = cfg.crop
    counts = [len(d) for d in dets_per_frame]
    total = sum(counts)
    if total == 0:
        return [np.zeros((0, cfg.embed_dim), np.float32)
                for _ in counts]
    from repro.core.detector import next_bucket
    with TRACER.span("track.crops", "track", args={"crops": total}) \
            if TRACER.enabled else NO_SPAN:
        npad = next_bucket(total, min_bucket=min_bucket)
        crops = np.zeros((npad, C, C, 3), np.float32)
        k = 0
        for frame, dets in zip(frames, dets_per_frame):
            if len(dets):
                crops[k:k + len(dets)] = extract_crops(frame, dets, C)
                k += len(dets)
    with TRACER.span("track.wait", "track",
                     args={"h2d_bytes": crops.nbytes}) \
            if TRACER.enabled else NO_SPAN:
        x = np.asarray(crop_embed(params, jnp.asarray(crops)))
    out = []
    k = 0
    for n in counts:
        out.append(x[k:k + n])
        k += n
    return out


# PR-1 name for ``embed_dets_chunk`` (same signature, kept for compat)
crop_embed_chunk = embed_dets_chunk
