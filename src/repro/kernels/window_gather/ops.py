"""Public window-gather ops with backend dispatch.

Two entry points:

  * ``window_gather`` — crop n same-size windows from ONE frame;
  * ``window_gather_batch`` — crop n same-size windows from a CHUNK of
    frames via a (frame, cy, cx) window table.  This is what the chunked
    execution engine calls: one dispatch per (size class, bucket) for the
    whole chunk.

Dispatch: on TPU the Pallas kernel runs natively; when the Pallas path is
forced off-TPU (``set_kernel_mode("pallas")``) the same kernel body runs
under ``interpret=True``.  The default CPU path is the memory-equivalent
vmapped ``dynamic_slice`` oracle.  The Pallas path takes cell-aligned
origins, matching the proxy's cell grid; the oracle takes pixels, so the
wrappers scale.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import jax

from repro.kernels import interpret, use_pallas
from repro.kernels.window_gather.kernel import (CELL, window_gather_pallas,
                                                window_gather_batch_pallas)
from repro.kernels.window_gather.ref import (window_gather_ref,
                                             window_gather_batch_ref)


@functools.partial(jax.jit, static_argnames=("win_h", "win_w", "cell"))
def window_gather(frame, cell_origins, *, win_h: int, win_w: int,
                  cell: int = CELL):
    """Crop n windows of (win_h, win_w) px from frame at cell-aligned
    origins.  frame: (H, W, C); cell_origins: (n, 2) int32 (cy, cx)."""
    if use_pallas():
        return window_gather_pallas(frame, cell_origins,
                                    win_h=win_h, win_w=win_w, cell=cell,
                                    interpret=interpret())
    return window_gather_ref(frame, cell_origins * cell,
                             win_h=win_h, win_w=win_w)


@functools.partial(jax.jit, static_argnames=("win_h", "win_w", "cell"))
def window_gather_batch(frames, window_table, *, win_h: int, win_w: int,
                        cell: int = CELL):
    """Crop n windows of (win_h, win_w) px from a chunk of frames.

    frames: (B, H, W, C); window_table: (n, 3) int32 rows
    (frame_idx, cy, cx) in CELL coordinates.  Returns
    (n, win_h, win_w, C)."""
    if use_pallas():
        return window_gather_batch_pallas(frames, window_table,
                                          win_h=win_h, win_w=win_w,
                                          cell=cell,
                                          interpret=interpret())
    tbl = window_table * jnp.asarray([1, cell, cell], jnp.int32)
    return window_gather_batch_ref(frames, tbl, win_h=win_h, win_w=win_w)
