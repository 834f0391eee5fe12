"""Public fused proxy-plan op with backend dispatch.

``proxy_plan(feat, w, b, threshold, grid_hw=(hc, wc))`` fuses the proxy
head (1x1 conv + sigmoid + threshold), the proxy->detector grid mapping
of ``pipeline.map_proxy_grid``, and the per-frame plan-stat reduction
into one device dispatch, so only the (B, hc, wc) int8 grid and a
(B, 8) int32 stats row cross back to the host — replacing the
score -> host -> ``map_proxy_grid`` -> ``plan_chunk`` round-trip over
the full (B, hp, wp) score map.

The span matrices replicate ``map_proxy_grid``'s source-span index
arithmetic exactly; both backends produce grids bit-identical to the
host path (integer span counts are exact in f32).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import interpret, use_pallas
from repro.kernels.proxy_plan.kernel import proxy_plan_pallas
from repro.kernels.proxy_plan.ref import proxy_plan_ref


@functools.lru_cache(maxsize=None)
def span_matrix(n_dst: int, n_src: int) -> np.ndarray:
    """(n_dst, n_src) 0/1 f32: row i covers ``map_proxy_grid``'s source
    span [ys_i, ye_i) of destination cell i."""
    idx = np.arange(n_dst)
    ys = np.minimum((idx * n_src) // n_dst, n_src - 1)
    ye = np.minimum(((idx + 1) * n_src + n_src - 1) // n_dst, n_src)
    ye = np.maximum(ye, ys + 1)
    src = np.arange(n_src)
    return ((src[None, :] >= ys[:, None])
            & (src[None, :] < ye[:, None])).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("grid_hw",))
def proxy_plan(feat, w, b, threshold, *, grid_hw):
    """feat: (B, hp, wp, C) proxy features; w: (C,); b, threshold:
    scalars; grid_hw: static (hc, wc) detector grid.

    Returns (mapped (B, hc, wc) int8, stats (B, 8) int32 rows
    [count, ymin, ymax, xmin, xmax, 0, 0, 0] over the mapped grid)."""
    hc, wc = grid_hw
    _, hp, wp, _ = feat.shape
    span_y = jnp.asarray(span_matrix(hc, hp))
    span_x = jnp.asarray(span_matrix(wc, wp))
    if use_pallas():
        return proxy_plan_pallas(feat, w, b, threshold, span_y, span_x,
                                 interpret=interpret())
    return proxy_plan_ref(feat, w, b, threshold, span_y, span_x)
