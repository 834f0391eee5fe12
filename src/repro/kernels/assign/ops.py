"""Public batched-assignment op with backend dispatch.

``assign_batch(costs)`` takes a stack of SQUARE finite cost matrices
(K, N, N) and returns the min-cost matched column per row, (K, N) int32 —
a full permutation per matrix.  Rectangular problems and forbidden pairs
are handled by the host wrapper ``repro.core.hungarian.hungarian_batch``,
which pads to square with a finite sentinel and filters afterwards.

Dispatch: Pallas on TPU (interpret=True when forced elsewhere); the
default CPU path is the same JV solver vmapped as plain jnp, so both
paths share one algorithm and tie-breaking.
"""
from __future__ import annotations

import jax

from repro.kernels import interpret, use_pallas
from repro.kernels.assign.kernel import assign_pallas, solve_one


_solve_vmapped = jax.jit(jax.vmap(solve_one))


@jax.jit
def assign_batch(costs):
    """costs: (K, N, N) finite f32 (all entries < hungarian.BIG/2).

    Returns (K, N) int32: matched column per row."""
    if use_pallas():
        return assign_pallas(costs, interpret=interpret())
    return _solve_vmapped(costs)
