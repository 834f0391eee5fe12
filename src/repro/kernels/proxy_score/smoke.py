"""CI smoke: proxy_score_pallas (interpret) vs the jnp oracle."""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels.proxy_score.kernel import proxy_score_pallas
from repro.kernels.proxy_score.ref import proxy_score_ref


def smoke() -> None:
    for B, Hc, Wc, C in [(2, 7, 13, 32), (3, 8, 8, 64)]:
        ks = jax.random.split(jax.random.PRNGKey(6), 2)
        feat = jax.random.normal(ks[0], (B, Hc, Wc, C))
        w = jax.random.normal(ks[1], (C,))
        sr, pr = proxy_score_ref(feat, w, 0.3, 0.5)
        sp, pp = proxy_score_pallas(feat, w, 0.3, 0.5, interpret=True)
        np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                                   atol=1e-6)
        # thresholded int8 grid must be exact (plan paths depend on it)
        np.testing.assert_array_equal(np.asarray(pp), np.asarray(pr))
