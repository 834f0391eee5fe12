"""Fused proxy-model head as a Pallas TPU kernel (the paper's §3.3 scorer).

The segmentation proxy ends with a 1x1 conv to one channel, a sigmoid, and
a threshold that yields the binary positive-cell grid.  Running these as
separate XLA ops costs two extra HBM round-trips of the (B, Hc, Wc) score
map; at proxy rates (every sampled frame) the head is bandwidth-bound, so
we fuse matvec + sigmoid + compare into one VMEM-resident epilogue.

Tiling: one grid step per frame.  The block is the frame's whole
(Hc, Wc, C) feature map with the (C, 1) weight column pinned to block 0;
both outputs are (1, Hc, Wc) blocks whose last two dims are the array's
own, which is what the TPU's (8, 128) tiling rule accepts for any cell
grid (rank-1 row blocks are refused).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _head_kernel(f_ref, w_ref, b_ref, t_ref, score_ref, pos_ref):
    f = f_ref[...][0].astype(jnp.float32)               # (Hc, Wc, C)
    hc, wc, C = f.shape
    w = w_ref[...].astype(jnp.float32)                  # (C, 1)
    logits = jax.lax.dot_general(
        f.reshape(hc * wc, C), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[:, 0] + b_ref[0]
    s = jax.nn.sigmoid(logits).reshape(hc, wc)
    score_ref[...] = s[None]
    pos_ref[...] = (s > t_ref[0]).astype(jnp.int8)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def proxy_score_pallas(feat, w, b, threshold, *, interpret: bool = False):
    """feat: (B, Hc, Wc, C); w: (C,); b, threshold: scalars.

    Returns (scores (B, Hc, Wc) fp32, positive (B, Hc, Wc) int8).
    """
    B, Hc, Wc, C = feat.shape
    return pl.pallas_call(
        _head_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hc, Wc, C), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, Hc, Wc), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Hc, Wc), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hc, Wc), jnp.float32),
            jax.ShapeDtypeStruct((B, Hc, Wc), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        name="proxy_score",
    )(feat, w.reshape(C, 1),
      jnp.asarray(b, jnp.float32).reshape(1),
      jnp.asarray(threshold, jnp.float32).reshape(1))
