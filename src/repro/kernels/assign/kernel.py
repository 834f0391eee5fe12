"""Batched min-cost assignment (Jonker-Volgenant) as a Pallas kernel.

One grid cell per cost matrix: the whole (N, N) matrix lives in VMEM and
the augmenting-path search runs as ``lax.while_loop``s over (1, N+1)
rows and an (N+1, 1) column — association matrices are tiny (N <=
max_tracks = 64), so a matrix is a single block and the batch axis is
embarrassingly parallel.

The solver mirrors ``repro.core.hungarian._hungarian_np`` (potentials +
augmenting paths, first-index argmin tie-break) but runs in float32 and
returns the FULL permutation; forbidden-pair filtering happens on the
wrapper side.  Callers must clamp sentinel costs to a finite value small
enough that f32 potential updates keep real cost differences resolvable
(see ``hungarian.hungarian_batch``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _at(vec, idx, j):
    """``vec[j]`` for a traced scalar ``j`` as one comparison and one
    reduction (exact: a single term survives the mask).  Mosaic lowers
    neither a dynamic gather nor a scatter on a value; this form runs
    unchanged in the Pallas body and under XLA."""
    if jnp.issubdtype(vec.dtype, jnp.floating):
        return jnp.max(jnp.where(idx == j, vec, -jnp.inf))
    return jnp.sum(jnp.where(idx == j, vec, 0))


def solve_padded(a, eff_n=None):
    """The JV core on the 1-indexed matrix: a (M, M) finite f32 with a
    dummy row and column 0 (M = N + 1) -> (M, 1) int32 matched column
    per row, rows 0..N-1 valid.

    Row-indexed state (the potential ``u``) is an (M, 1) column and
    column-indexed state (``v``, ``p``, ``way``, ``minv``, ``used``) a
    (1, M) row, so every update is an elementwise select and every
    index read is ``_at`` — no gather, no scatter, no transpose."""
    M = a.shape[0]
    N = M - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)    # column j
    rowv = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)    # row i
    eff = jnp.int32(N) if eff_n is None else \
        jnp.asarray(eff_n, jnp.int32)
    col_ok = lane <= eff
    inf = jnp.float32(jnp.inf)

    def outer(i, carry):
        u, v, p = carry
        # skipped rows (i > eff_n) park p[0] at 0: both while loops'
        # conditions are then false on entry, so the row is a no-op —
        # crucially WITHOUT lax.cond, which vmap turns into a select
        # that executes the loop body even for skipped rows (and an
        # all-masked argmin then never terminates)
        p = jnp.where(lane == 0, jnp.where(i <= eff, i, 0), p)

        def scan_cond(c):
            return _at(p, lane, c[0]) != 0

        def scan_body(c):
            j0, u, v, way, minv, used = c
            used = jnp.where(lane == j0, 1, used)
            i0 = _at(p, lane, j0)
            a_i0 = jnp.max(jnp.where(rowv == i0, a, -inf), axis=0,
                           keepdims=True)                # row i0, (1, M)
            cur = a_i0 - _at(u, rowv, i0) - v
            free = used == 0
            take = free & (cur < minv)
            minv = jnp.where(take, cur, minv)
            way = jnp.where(take, j0, way)
            masked = jnp.where(free & col_ok, minv, inf)
            delta = jnp.min(masked)
            j1 = jnp.min(jnp.where(masked == delta, lane, M))  # first on ties
            # u[p[j]] += delta over used columns j (matched rows are
            # distinct, so the (M, M) membership mask is a safe scatter)
            hit = (p == rowv) & (used > 0)
            row_hit = jnp.max(hit.astype(jnp.int32), axis=1,
                              keepdims=True) > 0
            u = jnp.where(row_hit, u + delta, u)
            v = jnp.where(used > 0, v - delta, v)
            minv = jnp.where(free, minv - delta, minv)
            return j1, u, v, way, minv, used

        j0, u, v, way, _, _ = jax.lax.while_loop(
            scan_cond, scan_body,
            (jnp.int32(0), u, v, jnp.zeros((1, M), jnp.int32),
             jnp.full((1, M), inf, jnp.float32),
             jnp.zeros((1, M), jnp.int32)))

        def aug_body(c):
            j0, p = c
            j1 = _at(way, lane, j0)
            return j1, jnp.where(lane == j0, _at(p, lane, j1), p)

        _, p = jax.lax.while_loop(lambda c: c[0] != 0, aug_body, (j0, p))
        return u, v, p

    _, _, p = jax.lax.fori_loop(
        1, M, outer, (jnp.zeros((M, 1), jnp.float32),
                      jnp.zeros((1, M), jnp.float32),
                      jnp.zeros((1, M), jnp.int32)))
    # invert p[j] = row matched to col j (1-indexed) into col per row;
    # rows no column holds (skipped, past eff_n) report col 0 like the
    # numpy twin
    hit = (p == rowv + 1) & (lane >= 1)
    return jnp.sum(jnp.where(hit, lane - 1, 0), axis=1, keepdims=True)


def solve_one(cost, eff_n=None):
    """cost: (N, N) finite f32 -> (N,) int32 matched column per row.

    Jonker-Volgenant with 1-indexed potential vectors, exactly the
    update order of ``_hungarian_np`` (so equal-cost tie-breaking
    matches the numpy path when the arithmetic is exact).

    ``eff_n`` (dynamic int32, default the static N) restricts the solve
    to the leading (eff_n, eff_n) submatrix: rows past it are skipped,
    columns past it never enter an argmin, so every f32 potential
    update touches exactly the values a direct (eff_n, eff_n) solve
    would — BIT-identical results regardless of the padded size N.
    That matters because JV arithmetic is NOT padding-invariant: a
    forced forbidden match pushes sentinel-scale deltas through the
    potentials, and f32 rounding of real-cost differences then depends
    on which padding columns the search walked.  Rows at or past
    ``eff_n`` report column 0."""
    a = jnp.pad(cost.astype(jnp.float32), ((1, 0), (1, 0)))  # row/col 0 dummy
    return solve_padded(a, eff_n)[:-1, 0]


def _assign_kernel(cost_ref, out_ref):
    out_ref[...] = solve_padded(cost_ref[...][0])[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def assign_pallas(costs, *, interpret: bool = False):
    """costs: (K, N, N) finite f32 -> (K, N) int32 column per row.

    The dummy row/column is padded on here, outside the kernel, and
    each matrix's block is its whole (N+1, N+1) plane with a
    (N+1, 1) result column: both keep the array's own last two dims,
    as the TPU's (8, 128) tiling requires."""
    K, N, M = costs.shape
    assert N == M, "assign kernel operates on square (padded) matrices"
    a = jnp.pad(costs.astype(jnp.float32), ((0, 0), (1, 0), (1, 0)))
    out = pl.pallas_call(
        _assign_kernel,
        grid=(K,),
        in_specs=[pl.BlockSpec((1, N + 1, N + 1), lambda k: (k, 0, 0))],
        out_specs=pl.BlockSpec((1, N + 1, 1), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, N + 1, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        name="assign",
    )(a)
    return out[:, :N, 0]
