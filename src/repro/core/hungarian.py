"""Hungarian algorithm (min-cost assignment), host-side numpy.

Classic O(n^3) potentials + augmenting-path formulation (Jonker-Volgenant
style).  Rectangular matrices are padded with a large cost; pairs matched
to padding are reported as unmatched.  Used by the recurrent tracker, the
SORT baseline, and the MOTA metric.

Hardware note (DESIGN.md §2): the paper runs Hungarian on the host CPU
next to a GPU; per-step association keeps that split by default.  The
batched Pallas solver (``repro.kernels.assign``) now covers the on-device
side: ``hungarian_batch`` solves a stack of independent problems in one
dispatch (MOTA's per-frame matrices, opt-in tracker assignment), and
``hungarian_on_device`` runs entirely on device instead of bridging
through ``jax.pure_callback``.
"""
from __future__ import annotations

import threading
from typing import List, Sequence, Tuple

import numpy as np

try:                                    # already in the image; optional
    from scipy.optimize import linear_sum_assignment as _lsa
except ImportError:                     # pragma: no cover
    _lsa = None

BIG = 1e9
# finite forbidden sentinel for the f32 device solver: large enough that
# any assignment using fewer forbidden edges wins (N * max real cost
# <= 64 * 2 << 2^13), small enough that f32 potential updates keep real
# cost differences resolvable
FORBIDDEN_DEVICE = 2.0 ** 13


def hungarian(cost: np.ndarray) -> List[Tuple[int, int]]:
    """cost: (n, m) -> list of (row, col) matched pairs (only real pairs;
    entries with cost >= BIG/2 are treated as forbidden).

    Dispatches to scipy's C implementation when available (it ships in
    the container); ``_hungarian_np`` is the dependency-free fallback.
    Both return a min-cost assignment — tie-breaking between equal-cost
    optima may differ, totals never do."""
    n, m = cost.shape
    if n == 0 or m == 0:
        return []
    if _lsa is not None:
        rows, cols = _lsa(cost)
        return [(int(r), int(c)) for r, c in zip(rows, cols)
                if cost[r, c] < BIG / 2]
    return _hungarian_np(cost)


def _hungarian_np(cost: np.ndarray) -> List[Tuple[int, int]]:
    """Pure-numpy Jonker-Volgenant: rectangular matrices are solved
    directly with rows = the SHORT side (transposing when n > m), so a
    few detections against max_tracks tracks runs min(n, m) augmenting
    paths instead of max(n, m).  Pairs come back row-sorted (the same
    ordering scipy's dispatch path emits)."""
    n, m = cost.shape
    if n == 0 or m == 0:
        return []
    if n > m:
        # invert the transposed solution with an O(n) counting pass —
        # the old path swapped axes then ran a full comparison sort on
        # output the solver had already ordered once
        col_of = np.full(n, -1, np.int64)
        for c, r in _hungarian_np(cost.T):
            col_of[r] = c
        return [(r, int(c)) for r, c in enumerate(col_of) if c >= 0]
    a = np.full((n + 1, m + 1), BIG, np.float64)
    a[1:, 1:] = cost
    u = np.zeros(n + 1)
    v = np.zeros(m + 1)
    p = np.zeros(m + 1, np.int64)         # p[j] = row matched to col j
    way = np.zeros(m + 1, np.int64)
    for i in range(1, n + 1):
        p[0] = i
        j0 = 0
        minv = np.full(m + 1, np.inf)
        used = np.zeros(m + 1, bool)
        while True:
            used[j0] = True
            i0 = p[j0]
            cur = a[i0, 1:] - u[i0] - v[1:]
            # vectorized column scan: update minv/way over unused columns
            # and pick the argmin (first index on ties, matching the
            # scalar loop this replaces — it dominated association cost
            # at max_tracks=64)
            free = ~used[1:]
            take = free & (cur < minv[1:])
            minv[1:][take] = cur[take]
            way[1:][take] = j0
            masked = np.where(free, minv[1:], np.inf)
            j1 = int(np.argmin(masked)) + 1
            delta = masked[j1 - 1]
            u[p[used]] += delta
            v[np.flatnonzero(used)] -= delta
            minv[~used] -= delta
            j0 = j1
            if p[j0] == 0:
                break
        while j0:
            j1 = way[j0]
            p[j0] = p[j1]
            j0 = j1
    # emit ROW-sorted (the contract, matching scipy) via linear inversion
    # of the col -> row matching instead of sorting afterwards
    col_of = np.full(n, -1, np.int64)
    for j in range(1, m + 1):
        i = int(p[j])
        if i >= 1 and cost[i - 1, j - 1] < BIG / 2:
            col_of[i - 1] = j - 1
    return [(r, int(c)) for r, c in enumerate(col_of) if c >= 0]


class _HostCounts(threading.local):
    """Per-thread work counters of the host JV twin (read as deltas by
    the caller that owns the thread's work, ``RecurrentTracker``)."""
    jv_steps = 0        # column-scan steps of ``solve_device_np``


COUNTS = _HostCounts()


def solve_device_np(cost: np.ndarray) -> np.ndarray:
    """Numpy float32 twin of ``kernels.assign.kernel.solve_one``: the
    same update order, the same first-index argmin tie-break and the
    same f32 arithmetic, so its output is bit-identical to the device
    solver on the same matrix.  cost: (N, N) finite f32 -> (N,) int32
    matched column per row (full permutation).

    Within one row's search the potentials of the rows and columns
    already on the alternating tree are written every step but never
    read: a row's ``u`` is read only in the step it joins, and a used
    column's reduced cost is masked out.  So those potentials ride in
    join-order buffers (``tu``, ``tv``), receive each step's ``delta``
    in the same order and precision, and go back to ``u``/``v`` when
    the search ends.  Meanwhile a used column parks ``v`` at -inf and
    ``minv`` at +inf, so its reduced cost is +inf and neither takes
    part in the compare nor wins the argmin: the masks of the kernel's
    formulation become plain comparisons."""
    cost = np.asarray(cost, np.float32)
    N = cost.shape[0]
    a = np.zeros((N + 1, N + 1), np.float32)
    a[1:, 1:] = cost
    a_rows = list(a)
    u = np.zeros(N + 1, np.float32)
    v = np.zeros(N + 1, np.float32)
    p = [0] * (N + 1)                   # p[j] = row matched to col j
    way = np.zeros(N + 1, np.int32)
    minv = np.empty(N + 1, np.float32)
    cur = np.empty(N + 1, np.float32)
    take = np.empty(N + 1, bool)
    tu = np.empty(N + 1, np.float32)    # u of the tree's rows, join order
    tv = np.empty(N + 1, np.float32)    # v of the tree's columns
    inf = np.float32(np.inf)
    steps = 0
    for i in range(1, N + 1):
        p[0] = i
        j0 = 0
        way.fill(0)
        minv.fill(inf)
        t_rows, t_cols = [], []
        k = 0
        while True:
            i0 = p[j0]
            t_rows.append(i0)
            t_cols.append(j0)
            tu[k] = u[i0]
            tv[k] = v[j0]
            v[j0] = -inf
            minv[j0] = inf
            k += 1
            np.subtract(a_rows[i0], u[i0], out=cur)
            np.subtract(cur, v, out=cur)
            np.less(cur, minv, out=take)
            np.putmask(minv, take, cur)
            np.putmask(way, take, j0)
            j0 = int(minv.argmin())     # first index on ties
            delta = minv[j0]
            tu[:k] += delta
            tv[:k] -= delta
            minv -= delta
            if p[j0] == 0:
                break
        steps += k
        u[t_rows] = tu[:k]
        v[t_cols] = tv[:k]
        while j0:
            j1 = int(way[j0])
            p[j0] = p[j1]
            j0 = j1
    COUNTS.jv_steps += steps
    col_of = np.zeros(N, np.int32)
    col_of[np.asarray(p[1:], np.intp) - 1] = np.arange(N, dtype=np.int32)
    return col_of


def assoc_side(n: int, m: int, min_bucket: int = 8) -> int:
    """Canonical square size for tracker association: the power-of-two
    bucket of max(n, m), floored at ``min_bucket``.  Every association
    path — this host twin, the per-frame fused kernel, and the chunk
    scan (via ``solve_one``'s dynamic ``eff_n``) — solves EXACTLY this
    square, because f32 JV results are not invariant to the padded
    size: a forced forbidden match pushes sentinel-scale deltas through
    the potentials, and the rounding of real-cost differences then
    depends on which padding columns the search walked."""
    side = max(1, min_bucket)
    need = max(n, m)
    while side < need:
        side *= 2
    return side


def hungarian_device_np(cost: np.ndarray) -> List[Tuple[int, int]]:
    """Host twin of the DEVICE association path: pad to the canonical
    ``assoc_side`` square with the finite ``FORBIDDEN_DEVICE``
    sentinel, solve with the f32 JV twin, filter forbidden pairs — the
    same contract as ``hungarian_batch`` for a batch of one, minus the
    device dispatch.

    Used by ``RecurrentTracker`` so that its pair selection (ties
    included) is bit-identical to ``kernels.track_step``'s on-device
    assignment, which restricts its solve to the same square via
    ``solve_one(eff_n=...)`` no matter how many slots its buffers
    carry."""
    n, m = cost.shape
    if n == 0 or m == 0:
        return []
    side = assoc_side(n, m)
    sq = np.full((side, side), FORBIDDEN_DEVICE, np.float32)
    sq[:n, :m] = np.minimum(cost, FORBIDDEN_DEVICE)
    cols = solve_device_np(sq)
    return [(r, int(cols[r])) for r in range(n)
            if cols[r] < m and cost[r, cols[r]] < BIG / 2]


def hungarian_batch(costs: Sequence[np.ndarray]
                    ) -> List[List[Tuple[int, int]]]:
    """Solve K independent (possibly rectangular) assignment problems in
    ONE device dispatch via the batched Pallas solver
    (``repro.kernels.assign``).

    Same contract as ``hungarian`` per problem: entries >= BIG/2 are
    forbidden and never reported.  Matrices are padded to a common
    square with the finite ``FORBIDDEN_DEVICE`` sentinel (the device
    solver runs f32, so real costs must stay << 2^13 — association
    costs here are <= 1).  Tie-breaking between equal-cost optima may
    differ from the host solvers; totals never do."""
    mats = [np.asarray(c, np.float32) for c in costs]
    if not mats:
        return []
    n_max = max((c.shape[0] for c in mats), default=0)
    m_max = max((c.shape[1] for c in mats), default=0)
    side = max(n_max, m_max)
    if side == 0 or all(c.shape[0] == 0 or c.shape[1] == 0 for c in mats):
        return [[] for _ in mats]
    from repro.kernels.assign import assign_batch   # lazy: jax + cycle

    batch = np.full((len(mats), side, side), FORBIDDEN_DEVICE, np.float32)
    for k, c in enumerate(mats):
        n, m = c.shape
        batch[k, :n, :m] = np.minimum(c, FORBIDDEN_DEVICE)
    cols = np.asarray(assign_batch(batch))
    out: List[List[Tuple[int, int]]] = []
    for k, c in enumerate(mats):
        n, m = c.shape
        out.append([(r, int(cols[k, r])) for r in range(n)
                    if cols[k, r] < m and c[r, cols[k, r]] < BIG / 2])
    return out


def hungarian_on_device(cost):
    """On-device assignment: col index per row (-1 = unmatched), computed
    entirely on device by the batched Pallas solver — no host callback.
    cost: (n, m) array with BIG-style forbidden entries."""
    import jax.numpy as jnp
    from repro.kernels.assign import assign_batch   # lazy: jax + cycle

    n, m = cost.shape
    side = max(n, m)
    c = jnp.minimum(cost.astype(jnp.float32), FORBIDDEN_DEVICE)
    c = jnp.pad(c, ((0, side - n), (0, side - m)),
                constant_values=FORBIDDEN_DEVICE)
    cols = assign_batch(c[None])[0][:n]
    orig = jnp.pad(cost.astype(jnp.float32), ((0, 0), (0, side - m)),
                   constant_values=np.float32(BIG))[:n]
    got = jnp.take_along_axis(orig, cols[:, None], axis=1)[:, 0]
    return jnp.where((cols < m) & (got < BIG / 2), cols, -1)
