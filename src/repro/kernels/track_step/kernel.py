"""Fused tracker-step kernel body (Pallas) and its shared jnp core.

One grid cell per stream: the slot state, the frame's padded detections
and the small tracker heads all fit in VMEM (Q <= a few hundred slots,
embed/rnn dims <= 128), so a step is a single block — detection
features, the (Q, Q) match-logit matrix, the cost assembly and the JV
assignment (``kernels.assign.solve_one``, run inline) plus both GRU
batches execute without touching HBM in between.  The batch axis (K
concurrent streams, the TrackBroker's consolidation axis) is
embarrassingly parallel.

Numerics contract: every transcendental and every multiply-add routes
through ``repro.core.fastmath``'s ``jx_*`` flavor, which is constructed
to be bit-identical to the numpy ``np_*`` flavor used by the host
tracker twins and by ``ref.py`` — that is what makes interpret == ref
exact and ``DeviceTracker`` == ``RecurrentTracker`` exact.

Slot layout (row space is RANK order — callers gather slots so live
tracks form a prefix in active-list order; dead rows trail):
  h_r      (Q, H)  GRU hidden state per ranked slot
  tbox_r   (Q, 4)  last box per ranked slot
  alive_r  (Q,)    1.0 live / 0.0 dead
  te_gap_r (Q,)    frames since the slot's last appended detection
  x        (Q, e)  crop embeddings, valid detections as a prefix
  dbox     (Q, 4)  detection boxes
  dvalid   (Q,)    1.0 real detection / 0.0 padding
  te_match (Q,)    frames since the previously processed frame
                   (broadcast scalar; 0 on the first frame)
Forbidden pairs (dead row, padding column, or match probability below
threshold) cost ``hungarian.FORBIDDEN_DEVICE``; pairs whose solved cost
is >= FORBIDDEN_DEVICE / 2 are reported unmatched (-1).  The JV solve
is restricted to the canonical ``hungarian.assoc_side`` square derived
from the LIVE/VALID counts (``solve_one``'s dynamic ``eff_n``), because
f32 JV is not padding-invariant; with that restriction, results are
invariant to the slot count (the broker pads streams to a common
bucket, the chunk scan carries max_tracks + D slots) and bit-identical
to the host's ``hungarian_device_np``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fastmath as fm
from repro.core.fastmath import jx_matmul as _dot
from repro.core.hungarian import FORBIDDEN_DEVICE
from repro.kernels.assign.kernel import solve_one


_F32 = jnp.float32
_EIGHTH = np.float32(0.125)
_ONE = np.float32(1.0)
_FORBID = np.float32(FORBIDDEN_DEVICE)
_HALF_FORBID = np.float32(FORBIDDEN_DEVICE / 2)


def _det_feats(x, boxes, te, dp_w, dp_b, table):
    """jnp twin of ``RecurrentTracker._det_feats_np``."""
    extra = jnp.stack([boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3],
                       te * _EIGHTH, fm.jx_log1p_int(te, table)], axis=1)
    d = jnp.concatenate([x, extra], axis=1)
    return fm.jx_tanh(_dot(d, dp_w) + dp_b)


def _gru(h, feat, wz, wr, wh, bz, br, bh):
    """jnp twin of ``RecurrentTracker._gru_np`` (single-multiply blend)."""
    hf = jnp.concatenate([feat, h], axis=-1)
    z = fm.jx_sigmoid(_dot(hf, wz) + bz)
    r = fm.jx_sigmoid(_dot(hf, wr) + br)
    hf2 = jnp.concatenate([feat, r * h], axis=-1)
    cand = fm.jx_tanh(_dot(hf2, wh) + bh)
    return h + z * (cand - h)


def step_core(h_r, tbox_r, alive_r, te_gap_r, te_match, x, dbox, dvalid,
              thr, dp_w, dp_b, wz, wr, wh, bz, br, bh,
              m_w0, m_b0, m_w1, m_b1, table):
    """One fused tracker step for one stream (shapes as in the module
    docstring; ``thr``/``table`` per ``ops.track_step``).

    Returns (matched_r (Q,) int32 det column per ranked row or -1,
    h_upd_r (Q, H) GRU update assuming the row matched its column,
    h_new (Q, H) GRU start state per detection column)."""
    Q, H = h_r.shape
    e = x.shape[1]
    feats_m = _det_feats(x, dbox, te_match, dp_w, dp_b, table)

    # relative features + match logits (twin of _match_np)
    d = dbox[None, :, :] - tbox_r[:, None, :]
    tesafe = jnp.maximum(te_match, _ONE)[None, :, None]
    rel = jnp.concatenate([d[..., :2], d[..., :2] / tesafe, d[..., 2:]],
                          axis=-1)
    pair = jnp.concatenate([
        jnp.broadcast_to(h_r[:, None], (Q, Q, H)),
        jnp.broadcast_to(feats_m[None], (Q, Q, e)),
        rel,
    ], axis=-1)
    hid = fm.jx_tanh(_dot(pair.reshape(Q * Q, -1), m_w0) + m_b0)
    logits = (_dot(hid, m_w1) + m_b1).reshape(Q, Q)

    # cost assembly: below-threshold, dead-row and padding-column pairs
    # all cost the finite device sentinel
    probs = fm.jx_sigmoid(logits)
    cost = jnp.where(probs >= thr, _ONE - probs, _FORBID)
    ok_pair = (alive_r[:, None] > 0) & (dvalid[None, :] > 0)
    cost = jnp.where(ok_pair, cost, _FORBID)

    # restrict the solve to the canonical assoc_side square (pow2
    # bucket of the live/valid counts, floor 8) so the result matches
    # the host twin bit for bit at ANY slot count Q
    t_cnt = jnp.sum(alive_r > 0).astype(jnp.int32)
    n_cnt = jnp.sum(dvalid > 0).astype(jnp.int32)
    need = jnp.maximum(jnp.maximum(t_cnt, n_cnt), 8)
    side = jax.lax.fori_loop(
        0, 16, lambda _, s: jnp.where(s < need, s * 2, s), jnp.int32(8))
    cols = solve_one(cost, eff_n=jnp.minimum(side, Q))
    got = jnp.take_along_axis(cost, cols[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    matched_r = jnp.where(got < _HALF_FORBID, cols, -1).astype(jnp.int32)

    # GRU updates: matched rows against their solved column (within-track
    # gap te), new-track starts against every column (te = 0, h = 0);
    # rows are per-sample independent, so callers select what applies
    xg = jnp.take(x, cols, axis=0)
    bg = jnp.take(dbox, cols, axis=0)
    feats_g = _det_feats(xg, bg, te_gap_r, dp_w, dp_b, table)
    h_upd_r = _gru(h_r, feats_g, wz, wr, wh, bz, br, bh)
    feats_0 = _det_feats(x, dbox, jnp.zeros_like(te_match), dp_w, dp_b,
                         table)
    h_new = _gru(jnp.zeros_like(h_r), feats_0, wz, wr, wh, bz, br, bh)
    return matched_r, h_upd_r, h_new


def _track_step_kernel(h_ref, tbox_ref, alive_ref, te_gap_ref,
                       te_match_ref, x_ref, dbox_ref, dvalid_ref, thr_ref,
                       dpw_ref, dpb_ref, wz_ref, wr_ref, wh_ref, bz_ref,
                       br_ref, bh_ref, mw0_ref, mb0_ref, mw1_ref, mb1_ref,
                       tab_ref, matched_ref, hupd_ref, hnew_ref):
    matched, h_upd, h_new = step_core(
        h_ref[...][0], tbox_ref[...][0], alive_ref[...][0],
        te_gap_ref[...][0], te_match_ref[...][0], x_ref[...][0],
        dbox_ref[...][0], dvalid_ref[...][0], thr_ref[...][0, 0],
        dpw_ref[...], dpb_ref[...], wz_ref[...], wr_ref[...], wh_ref[...],
        bz_ref[...], br_ref[...], bh_ref[...], mw0_ref[...], mb0_ref[...],
        mw1_ref[...], mb1_ref[...], tab_ref[...][:, 0])
    matched_ref[...] = matched[None]
    hupd_ref[...] = h_upd[None]
    hnew_ref[...] = h_new[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def track_step_pallas(h_r, tbox_r, alive_r, te_gap_r, te_match, x, dbox,
                      dvalid, thr, params, table, *,
                      interpret: bool = False):
    """Batched fused step: leading K axis on the 8 stream arrays; the 12
    head parameters, the threshold (1, 1) and the log1p table (T, 1) are
    shared across the grid."""
    K, Q, H = h_r.shape
    e = x.shape[2]

    def stream(shape):
        return pl.BlockSpec((1,) + shape, lambda k: (k,) + (0,) * len(shape))

    def shared(arr):
        nd = arr.ndim
        return pl.BlockSpec(arr.shape, lambda k: (0,) * nd)

    in_specs = [
        stream((Q, H)), stream((Q, 4)), stream((Q,)), stream((Q,)),
        stream((Q,)), stream((Q, e)), stream((Q, 4)), stream((Q,)),
        shared(thr),
    ] + [shared(p) for p in params] + [shared(table)]
    return pl.pallas_call(
        _track_step_kernel,
        grid=(K,),
        in_specs=in_specs,
        out_specs=(stream((Q,)), stream((Q, H)), stream((Q, H))),
        out_shape=(jax.ShapeDtypeStruct((K, Q), jnp.int32),
                   jax.ShapeDtypeStruct((K, Q, H), _F32),
                   jax.ShapeDtypeStruct((K, Q, H), _F32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        name="track_step",
    )(h_r, tbox_r, alive_r, te_gap_r, te_match, x, dbox, dvalid, thr,
      *params, table)
