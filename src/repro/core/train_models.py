"""Offline model training for the pipeline: detector pre-training, proxy
training (on θ_best detections), and tracker training (on θ_best tracks).

The paper assumes a PRE-TRAINED detector (YOLOv3 etc.); here the stand-in
detector is trained once per dataset on synthetic ground truth — this cost
sits outside the benchmarked runtime exactly like the paper's pretrained
weights.  Proxy and tracker training follow the paper: labels come from
the θ_best configuration's outputs, never from ground truth.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import detector as det_mod
from repro.data.video_synth import Clip
from repro.optim import adamw


def _fit(loss_fn, params, batches, lr: float = 3e-3, log=None):
    """Generic Adam fit: batches is an iterable of arg-tuples."""
    opt = adamw(lr=lr, weight_decay=0.0)
    state = opt.init(params)
    vg = jax.jit(jax.value_and_grad(loss_fn))
    losses = []
    for args in batches:
        loss, g = vg(params, *args)
        params, state = opt.update(g, state, params)
        losses.append(float(loss))
        if log and len(losses) % 50 == 0:
            log(f"  step {len(losses)} loss {np.mean(losses[-50:]):.4f}")
    return params, losses


# ---------------------------------------------------------------------------
# Detector
# ---------------------------------------------------------------------------

def train_detector(arch: str, clips: Sequence[Clip],
                   resolutions: Sequence[Tuple[int, int]],
                   steps: int = 240, batch: int = 8, seed: int = 0,
                   lr: float = 3e-3) -> det_mod.Detector:
    """Multi-resolution detector pre-training on synthetic GT boxes.
    Frames come through the pipeline's render cache, so a step renders
    only frames no earlier step drew."""
    from repro.core.pipeline import render_frame
    params = det_mod.init_detector(arch, seed)
    rng = np.random.default_rng(seed)
    S = det_mod.STRIDE

    def batches():
        for step in range(steps):
            W, H = resolutions[step % len(resolutions)]
            hc, wc = H // S, W // S
            frames, boxes = [], []
            for _ in range(batch):
                clip = clips[rng.integers(len(clips))]
                f = int(rng.integers(clip.n_frames))
                frames.append(render_frame(clip, f, W, H)[0])
                boxes.append(clip.boxes_at(f))
            obj, box = det_mod.make_targets(boxes, hc, wc)
            yield (jnp.asarray(np.stack(frames)), jnp.asarray(obj),
                   jnp.asarray(box))

    loss_fn = lambda p, f, o, b: det_mod.detector_loss(p, f, o, b, arch)  # noqa
    params, losses = _fit(loss_fn, params, batches(), lr=lr)
    return det_mod.Detector(arch, params), losses


def detector_f1(detector: det_mod.Detector, clips: Sequence[Clip],
                res: Tuple[int, int], conf: float = 0.4,
                n_frames: int = 40) -> float:
    """Quick detection quality check against GT (IoU>=0.3 matching)."""
    tp = fp = fn = 0
    rng = np.random.default_rng(1)
    for _ in range(n_frames):
        clip = clips[rng.integers(len(clips))]
        f = int(rng.integers(clip.n_frames))
        frame = clip.render(f, res[0], res[1])
        dets = detector.detect_batch(frame[None], conf)[0]
        gt = clip.boxes_at(f)
        iou = det_mod.iou_matrix(dets[:, :4], gt[:, :4])
        matched_gt = set()
        for i in np.argsort(-dets[:, 4] if len(dets) else []):
            j = int(np.argmax(iou[i])) if iou.shape[1] else -1
            if j >= 0 and iou[i, j] >= 0.3 and j not in matched_gt:
                matched_gt.add(j)
                tp += 1
            else:
                fp += 1
        fn += len(gt) - len(matched_gt)
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn, 1)
    return 2 * prec * rec / max(prec + rec, 1e-9)
