"""Public fused proxy-head op with backend dispatch."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret, use_pallas
from repro.kernels.proxy_score.kernel import proxy_score_pallas
from repro.kernels.proxy_score.ref import proxy_score_ref


@jax.jit
def proxy_score(feat, w, b, threshold):
    """Fused 1x1-conv + sigmoid + threshold -> (scores, positive grid).

    feat: (B, Hc, Wc, C); w: (C,); b, threshold: scalars.
    """
    if use_pallas():
        return proxy_score_pallas(feat, w, b, threshold,
                                  interpret=interpret())
    return proxy_score_ref(feat, w, b, threshold)
