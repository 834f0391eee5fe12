"""Fused proxy-score + threshold + window-plan grid as a Pallas kernel.

Extends the ``proxy_score`` fusion one stage further (§3.3 -> §3.4): the
positive-cell grid never leaves the device.  One grid cell per frame:

  matvec head (MXU) -> sigmoid -> threshold        (as proxy_score)
  span_y @ pos @ span_x^T > 0                      (map to detector grid)
  count + bbox reduction over the mapped grid      (plan stats)

The span matrices are 0/1 constants from ``map_proxy_grid``'s index
arithmetic, so the two small matmuls compute exact integer span-counts —
"any positive in span" is count > 0, bit-identical to the host
integral-image path.  The (B, 8) int32 stats row [count, ymin, ymax,
xmin, xmax, 0, 0, 0] lets the host planner emit the window list for the
common single-cluster case without touching the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.proxy_plan.ref import STATS_W


def _plan_kernel(f_ref, w_ref, b_ref, t_ref, sy_ref, sx_ref,
                 grid_ref, stats_ref):
    f = f_ref[...][0].astype(jnp.float32)               # (hp, wp, C)
    hp, wp, C = f.shape
    w = w_ref[...].astype(jnp.float32)                  # (C, 1)
    logits = jax.lax.dot_general(
        f.reshape(hp * wp, C), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[:, 0] + b_ref[0]
    s = jax.nn.sigmoid(logits)
    pos = (s > t_ref[0]).astype(jnp.float32).reshape(hp, wp)
    sy = sy_ref[...]                                    # (hc, hp)
    sx = sx_ref[...]                                    # (wc, wp)
    cnt = jax.lax.dot_general(
        sy, pos, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (hc, wp)
    cnt = jax.lax.dot_general(
        cnt, sx, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (hc, wc)
    mapped = cnt > 0.5
    hc, wc = mapped.shape
    grid_ref[...] = mapped.astype(jnp.int8)[None]
    ri = jax.lax.broadcasted_iota(jnp.int32, (hc, wc), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (hc, wc), 1)
    count = jnp.sum(mapped.astype(jnp.int32))
    ymin = jnp.min(jnp.where(mapped, ri, hc))
    ymax = jnp.max(jnp.where(mapped, ri, -1))
    xmin = jnp.min(jnp.where(mapped, ci, wc))
    xmax = jnp.max(jnp.where(mapped, ci, -1))
    zero = count * 0
    stats_ref[...] = jnp.stack(
        [count, ymin, ymax, xmin, xmax, zero, zero, zero])[None, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def proxy_plan_pallas(feat, w, b, threshold, span_y, span_x, *,
                      interpret: bool = False):
    """feat: (B, hp, wp, C); w: (C,); b, threshold: scalars;
    span_y: (hc, hp) f32; span_x: (wc, wp) f32.

    Returns (mapped (B, hc, wc) int8, stats (B, STATS_W) int32).

    Stats are written as (B, 1, STATS_W) so each frame's block keeps the
    array's own last two dims, as the TPU's (8, 128) tiling requires."""
    B, hp, wp, C = feat.shape
    hc, wc = span_y.shape[0], span_x.shape[0]
    mapped, stats = pl.pallas_call(
        _plan_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, hp, wp, C), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((hc, hp), lambda i: (0, 0)),
            pl.BlockSpec((wc, wp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hc, wc), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, STATS_W), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, hc, wc), jnp.int8),
            jax.ShapeDtypeStruct((B, 1, STATS_W), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        name="proxy_plan",
    )(feat, w.reshape(C, 1),
      jnp.asarray(b, jnp.float32).reshape(1),
      jnp.asarray(threshold, jnp.float32).reshape(1),
      span_y.astype(jnp.float32), span_x.astype(jnp.float32))
    return mapped, stats.reshape(B, STATS_W)
