"""Public fused track-step op with backend dispatch.

``track_step(...)`` computes one tracker step for K concurrent streams
in ONE dispatch: detection features, match logits, cost assembly, JV
assignment and both GRU batches (see ``kernel.py`` for the slot layout
and ``kernels/README.md`` for the contract).

Dispatch: ``TPU_PALLAS`` is False, so a TPU runs ``step_core``
vmapped under XLA, as the CPU does; the Pallas body runs only when
forced (``set_kernel_mode("pallas")``, interpreted off the TPU).  Both
paths share one algorithm bit for bit.  ``ref.py`` is the numpy
oracle.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import numpy as np

from repro.core import fastmath as fm
from repro.kernels import interpret, use_pallas
from repro.kernels.track_step.kernel import step_core, track_step_pallas

# flat operand order for the tracker heads, as produced by
# ``core.tracker._host_params``; biases are reshaped to (1, n)
PARAM_ORDER: Tuple[str, ...] = (
    "det_proj/w", "det_proj/b",
    "gru/wz", "gru/wr", "gru/wh", "gru/bz", "gru/br", "gru/bh",
    "match/w0", "match/b0", "match/w1", "match/b1")

# the log1p-of-integer-gap table as a kernel operand, (T, 1) f32
LOG1P_TABLE_2D = fm.LOG1P_TABLE[:, None]


def pack_params(np_params: Dict[str, np.ndarray]
                ) -> Tuple[np.ndarray, ...]:
    """Flatten ``_host_params`` output into the kernel operand tuple."""
    out = []
    for key in PARAM_ORDER:
        v = np.asarray(np_params[key], np.float32)
        if v.ndim == 1:
            v = v[None, :]
        out.append(v)
    return tuple(out)


# The fused body does not fit the TPU's kernel compiler: ``jx_matmul``
# materializes (k, Q*Q, m) rank-1 products (over 400 MB at Q=128, far
# past VMEM) and indexes them, the log1p table and the solved columns
# with dynamic gathers Mosaic does not lower.  The TPU therefore runs
# the same ``step_core`` under XLA.
TPU_PALLAS = False

_step_vmapped = jax.jit(jax.vmap(step_core,
                                 in_axes=(0,) * 8 + (None,) * 14))


@jax.jit
def track_step(h_r, tbox_r, alive_r, te_gap_r, te_match, x, dbox, dvalid,
               thr, params, table):
    """h_r (K, Q, H), tbox_r (K, Q, 4), alive_r/te_gap_r/te_match/dvalid
    (K, Q), x (K, Q, e), dbox (K, Q, 4) f32; thr (1, 1) f32; params the
    ``pack_params`` tuple; table (T, 1) f32 (``LOG1P_TABLE_2D``).

    Returns (matched (K, Q) int32 det column per ranked row or -1,
    h_upd (K, Q, H), h_new (K, Q, H))."""
    if use_pallas(TPU_PALLAS):
        return track_step_pallas(h_r, tbox_r, alive_r, te_gap_r, te_match,
                                 x, dbox, dvalid, thr, params, table,
                                 interpret=interpret())
    return _step_vmapped(h_r, tbox_r, alive_r, te_gap_r, te_match, x,
                         dbox, dvalid, thr, *params, table[:, 0])
