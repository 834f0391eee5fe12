"""The tracker's host twins against frozen copies of their line-by-line
ports: ``fastmath.np_fmadd`` (exact fma, ties-only correction) and the
transcendentals built on it, ``hungarian.solve_device_np`` (the f32 JV
twin), and a whole ``RecurrentTracker`` sequence.  Every comparison is
bit for bit."""
import dataclasses

import numpy as np
import pytest

from repro.configs.multiscope import TrackerConfig
from repro.core import fastmath as fm
from repro.core import hungarian as hg
from repro.core.hungarian import BIG, FORBIDDEN_DEVICE
from repro.core.tracker import RecurrentTracker, init_tracker


# ---------------------------------------------------------------------------
# frozen copies: the twins as they were before the fast paths
# ---------------------------------------------------------------------------

def frozen_fmadd(a, b, c):
    a64 = np.asarray(a, np.float64)
    b64 = np.asarray(b, np.float64)
    c64 = np.asarray(c, np.float64)
    p = a64 * b64
    s = p + c64
    bv = s - p
    err = (p - (s - bv)) + (c64 - bv)
    s = np.ascontiguousarray(np.broadcast_to(s, err.shape))
    bits = s.view(np.int64)
    fix = (err != 0) & ((bits & 1) == 0) & np.isfinite(s)
    dirn = np.where(err > 0, np.float64(np.inf), np.float64(-np.inf))
    s = np.where(fix, np.nextafter(s, dirn), s)
    return s.astype(np.float32)


def frozen_exp(x):
    x = np.clip(np.asarray(x, np.float32), fm._EXP_LO, fm._EXP_HI)
    k = np.floor(frozen_fmadd(x, fm._LOG2E, fm._HALF))
    r = frozen_fmadd(k, -fm._LN2_HI, x)
    r = frozen_fmadd(k, -fm._LN2_LO, r)
    p = frozen_fmadd(fm._EXP_POLY[0], r, fm._EXP_POLY[1])
    for c in fm._EXP_POLY[2:]:
        p = frozen_fmadd(p, r, c)
    s = frozen_fmadd(p, r * r, r) + fm._ONE
    return (s * fm._np_pow2(k)).astype(np.float32)


def frozen_sigmoid(x):
    x = np.clip(np.asarray(x, np.float32), -fm._SIG_CLAMP, fm._SIG_CLAMP)
    return fm._ONE / (fm._ONE + frozen_exp(-x))


def frozen_tanh(x):
    return fm._TWO * frozen_sigmoid(fm._TWO * np.asarray(x, np.float32)) \
        - fm._ONE


def frozen_solve(cost, counter=None):
    cost = np.asarray(cost, np.float32)
    N = cost.shape[0]
    a = np.zeros((N + 1, N + 1), np.float32)
    a[1:, 1:] = cost
    rows1 = np.arange(N + 1, dtype=np.int32)
    u = np.zeros(N + 1, np.float32)
    v = np.zeros(N + 1, np.float32)
    p = np.zeros(N + 1, np.int32)
    for i in range(1, N + 1):
        p[0] = i
        j0 = 0
        way = np.zeros(N + 1, np.int32)
        minv = np.full(N + 1, np.inf, np.float32)
        used = np.zeros(N + 1, bool)
        while p[j0] != 0:
            if counter is not None:
                counter[0] += 1
            used[j0] = True
            i0 = p[j0]
            cur = (a[i0] - u[i0]) - v
            free = ~used
            take = free & (cur < minv)
            minv = np.where(take, cur, minv)
            way = np.where(take, j0, way).astype(np.int32)
            masked = np.where(free, minv, np.float32(np.inf))
            j1 = int(np.argmin(masked))
            delta = masked[j1]
            row_hit = ((p[None, :] == rows1[:, None])
                       & used[None, :]).any(1)
            u = np.where(row_hit, u + delta, u).astype(np.float32)
            v = np.where(used, v - delta, v).astype(np.float32)
            minv = np.where(free, minv - delta, minv).astype(np.float32)
            j0 = j1
        while j0:
            j1 = way[j0]
            p[j0] = p[j1]
            j0 = j1
    col_of = np.zeros(N, np.int32)
    col_of[p[1:] - 1] = np.arange(N, dtype=np.int32)
    return col_of


def _same_bits(got, want, same_shape=True):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not same_shape:      # the frozen copies return 0-d results as (1,)
        got, want = got.reshape(-1), want.reshape(-1)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


# ---------------------------------------------------------------------------
# exact fma: fast path vs the exact helper
# ---------------------------------------------------------------------------

def _ties(rng, n):
    """a*b + c whose f64 sum lands exactly on an f32 rounding midpoint
    with a nonzero residual of either sign: c = +-X (X a random normal
    f32 of ulp 2^(e-23)) and a*b = +-2^(e-24) * (1 - 2^-46)."""
    e = rng.integers(-80, 100, n)           # keeps b normal
    mant = rng.integers(1 << 23, 1 << 24, n).astype(np.float64)
    x = np.ldexp(mant, e - 23).astype(np.float32)
    sgn = rng.choice([-1.0, 1.0], n)
    resid = rng.choice([-1.0, 1.0], n)       # side of the midpoint
    e1 = rng.integers(-20, 20, n)
    a = (resid * sgn * np.ldexp(1 + 2.0 ** -23, e1)).astype(np.float32)
    b = np.ldexp(1 - 2.0 ** -23, e - 24 - e1).astype(np.float32)
    return a, b, (sgn * x).astype(np.float32)


def _fma_case(name, rng):
    n = 20000
    if name == "random":
        a, b, c = (rng.standard_normal((3, n))
                   * 10.0 ** rng.integers(-8, 8, (3, n))).astype(np.float32)
        return a, b, c
    if name == "ties":
        return _ties(rng, n)
    if name == "subnormal":
        a = (rng.standard_normal(n) * 2.0 ** -70).astype(np.float32)
        b = (rng.standard_normal(n) * 2.0 ** -70).astype(np.float32)
        c = (rng.standard_normal(n) * 2.0 ** -135).astype(np.float32)
        return a, b, c
    if name == "inf_nan":
        sp = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-45, -1e-45,
                       1.1754944e-38, 3.4028235e38, -3.4028235e38, 1.0,
                       -2.5], np.float32)
        return tuple(g.ravel() for g in np.meshgrid(sp, sp, sp))
    if name == "broadcast_exp":
        x = np.clip((rng.standard_normal((64, 48)) * 20).astype(np.float32),
                    fm._EXP_LO, fm._EXP_HI)
        return x, fm._LOG2E, fm._HALF
    if name == "broadcast_scalar_first":
        r = (rng.standard_normal((37, 5)) * 0.3).astype(np.float32)
        return fm._EXP_POLY[0], r, fm._EXP_POLY[1]
    if name == "broadcast_rows":
        z = rng.random((12, 64)).astype(np.float32)
        d = rng.standard_normal((1, 64)).astype(np.float32)
        h = rng.standard_normal((12, 1)).astype(np.float32)
        return z, d, h
    if name == "scalars":
        return (np.float32(1 + 2 ** -23),
                np.float32(2 ** -24 * (1 - 2 ** -23)), np.float32(1.0))
    raise AssertionError(name)


FMA_CASES = ["random", "ties", "subnormal", "inf_nan", "broadcast_exp",
             "broadcast_scalar_first", "broadcast_rows", "scalars"]


@pytest.mark.parametrize("case", FMA_CASES)
def test_fmadd_fast_path_matches_exact(case):
    """``np_fmadd`` is bit-identical to the kept exact helper (and to
    the frozen original) on every input class, and counts exactly the
    elements it routed through the exact path."""
    rng = np.random.default_rng(FMA_CASES.index(case))
    a, b, c = _fma_case(case, rng)
    with np.errstate(all="ignore"):
        t0 = fm.COUNTS.fma_ties
        got = fm.np_fmadd(a, b, c)
        ties = fm.COUNTS.fma_ties - t0
        want = fm._np_fmadd_exact(a, b, c)
        frozen = frozen_fmadd(a, b, c)
        s = np.add(np.multiply(a, b, dtype=np.float64), c,
                   dtype=np.float64)
    scalar = case == "scalars"
    _same_bits(got, want, same_shape=not scalar)
    _same_bits(got, frozen, same_shape=not scalar)
    assert isinstance(got, np.ndarray)
    if case == "ties":
        # the construction really puts the f64 sum on a midpoint, and
        # about half of them round wrongly without the correction
        assert ties == len(a)
        assert np.count_nonzero(
            np.asarray(s).astype(np.float32) != want) > len(a) // 4
    elif case == "subnormal":
        assert ties == np.count_nonzero(
            (np.abs(s) < 2.0 ** -126) & (s != 0))
    elif case in ("random", "broadcast_exp", "broadcast_rows"):
        assert ties < np.size(got) // 100
    elif case == "scalars":
        assert ties == 1 and got.shape == ()


TRANSCENDENTALS = {"exp": (fm.np_exp, frozen_exp),
                   "sigmoid": (fm.np_sigmoid, frozen_sigmoid),
                   "tanh": (fm.np_tanh, frozen_tanh)}


@pytest.mark.parametrize("name", sorted(TRANSCENDENTALS))
def test_transcendentals_match_frozen(name):
    """exp / sigmoid / tanh over a dense grid, random scales, the
    clamps and special values: the same bits as the frozen originals."""
    new, old = TRANSCENDENTALS[name]
    rng = np.random.default_rng(7)
    grid = np.linspace(-100, 100, 200001, dtype=np.float32)
    rand = (rng.standard_normal(100000)
            * 10.0 ** rng.integers(-6, 3, 100000)).astype(np.float32)
    special = np.array([0.0, -0.0, 1e-45, -1e-45, 30.0, -30.0, 87.0,
                        88.0, -87.0, 1e30, -1e30, np.inf, -np.inf],
                       np.float32)
    for x in (grid, rand, special, grid[1:].reshape(400, -1)[:, :7],
              np.float32(0.3)):
        with np.errstate(all="ignore"):
            _same_bits(new(x), old(x), same_shape=np.ndim(x) > 0)


# ---------------------------------------------------------------------------
# JV twin vs the frozen line-by-line port
# ---------------------------------------------------------------------------

def _square(rng, side, kind):
    """A padded square as ``hungarian_device_np`` builds it: an (n, m)
    tracker-like cost (1 - prob where prob clears the threshold, BIG
    elsewhere), clipped and padded with ``FORBIDDEN_DEVICE``."""
    n = int(rng.integers(1, side + 1))
    m = int(rng.integers(1, side + 1))
    if kind == "ties":                      # few distinct costs
        prob = rng.integers(1, 9, (n, m)) / 8.0
    else:
        prob = rng.random((n, m))
    cost = np.where(prob >= 0.2, 1.0 - prob, BIG).astype(np.float32)
    if kind == "dense":
        cost = (1.0 - prob).astype(np.float32)
    sq = np.full((side, side), FORBIDDEN_DEVICE, np.float32)
    sq[:n, :m] = np.minimum(cost, FORBIDDEN_DEVICE)
    return sq


@pytest.mark.parametrize("side,count", [(8, 200), (16, 180), (32, 90),
                                        (64, 40)])
def test_jv_twin_matches_frozen_port(side, count):
    """510 squares over sides 8-64, with forbidden padding, exact ties
    and dense costs: the same column per row as the frozen port, and
    ``COUNTS.jv_steps`` advances by its column-scan steps."""
    rng = np.random.default_rng(side)
    for k in range(count):
        sq = _square(rng, side, ("padded", "ties", "dense")[k % 3])
        steps = [0]
        want = frozen_solve(sq, steps)
        s0 = hg.COUNTS.jv_steps
        got = hg.solve_device_np(sq)
        assert hg.COUNTS.jv_steps - s0 == steps[0]
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# a whole tracker sequence
# ---------------------------------------------------------------------------

def _sequence(rng, n_obj, frames, e):
    pos = rng.random((3 * n_obj, 2)).astype(np.float32)
    vel = (rng.standard_normal((3 * n_obj, 2)) * 0.01).astype(np.float32)
    base = rng.standard_normal((3 * n_obj, e)).astype(np.float32)
    dets, embeds = [], []
    for k in range(frames):
        n = 0 if k % 17 == 5 else n_obj + int(rng.integers(-2, 3))
        ids = rng.permutation(3 * n_obj)[:n]
        d = np.zeros((n, 5), np.float32)
        d[:, :2] = pos[ids] + vel[ids] * k
        d[:, 2:4] = 0.05
        d[:, 4] = 0.9
        dets.append(d)
        embeds.append((base[ids] + 0.05 * rng.standard_normal(
            (n, e))).astype(np.float32))
    return dets, embeds


@pytest.mark.parametrize("n_obj,max_tracks", [(10, 64), (6, 8)])
def test_tracker_sequence_matches_frozen_twins(monkeypatch, n_obj,
                                               max_tracks):
    """64 frames through ``RecurrentTracker.step`` at the paper's
    tracker widths: tracks, GRU states and counters with the fast
    twins equal those with the frozen ones, bit for bit."""
    cfg = dataclasses.replace(TrackerConfig(), max_tracks=max_tracks)
    params = init_tracker(cfg, seed=3)
    dets, embeds = _sequence(np.random.default_rng(n_obj), n_obj, 64,
                             cfg.embed_dim)

    def run():
        tr = RecurrentTracker(cfg, params)
        for k in range(len(dets)):
            tr.step(k, dets[k], None, det_embeds=embeds[k])
        return tr

    fast = run()
    with monkeypatch.context() as mp:
        mp.setattr(fm, "np_fmadd", frozen_fmadd)
        mp.setattr(hg, "solve_device_np", frozen_solve)
        slow = run()
    assert fast.jv_steps > 0 and slow.jv_steps == 0
    a, b = fast.result(), slow.result()
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        _same_bits(x, y)
    states = [t.h for t in fast.finished + fast.active]
    want = [t.h for t in slow.finished + slow.active]
    assert len(states) == len(want)
    for x, y in zip(states, want):
        _same_bits(x, y)
