"""Bitwise-matched host/device elementwise math for the tracker twins.

The recurrent tracker runs its small heads twice: in numpy on the host
(``RecurrentTracker``'s ``_*_np`` twins) and in jnp inside the fused
``kernels.track_step`` kernel.  The repo's correctness bar is BIT
equality between the two, which ordinary ``np.tanh`` vs XLA ``tanh``
cannot give (different polynomial approximations), and which plain
``a * b + c`` cannot give either (XLA CPU contracts the multiply-add
into a hardware fma; numpy rounds twice).

This module pins one shared algorithm per function and gives each a
``np_*`` (host) and ``jx_*`` (traced) flavor constructed to produce
identical f32 bits:

* ``fmadd`` — the only multiply-feeding-an-add pattern either flavor is
  allowed to write.  The jnp flavor is literally ``a * b + c`` (XLA
  contracts it to a single-rounding fma); the numpy flavor emulates that
  fma exactly in f64 (the 24+24-bit product is exact in f64): one f64
  add and the f32 cast, with the few elements whose f64 sum lies on an
  f32 rounding midpoint corrected by Boldo-Melquiond round-to-odd (a
  TwoSum residual decides the odd-rounding nudge before the cast).
* ``exp`` — Cody-Waite range reduction + the Cephes ``expf`` degree-5
  polynomial, every step either an ``fmadd`` or an exact op (floor,
  clip, power-of-two scale built by integer exponent bit-twiddling).
* ``sigmoid`` — ``1 / (1 + exp(-x))`` with the input clamped to
  [-30, 30] so ``exp`` stays comfortably normal (no subnormal/FTZ
  divergence) and the ``1 + e`` add never meets a rounded product.
* ``tanh`` — ``2 * sigmoid(2x) - 1``: both multiplies are by powers of
  two (exact), so even if XLA contracts ``2*s - 1`` into an fma the
  result is unchanged.
* ``log1p_int`` — the tracker only ever takes ``log1p`` of integer
  frame gaps, so a 4096-entry f32 table (computed once in f64) replaces
  the libm call; gaps beyond the table clamp to the last entry.
* ``matmul`` — BLAS ``@`` and XLA's ``dot`` disagree bitwise in a
  shape-dependent way (blocked SIMD accumulation vs Eigen kernels), so
  neither may appear on a bit-matched path.  The pinned algorithm is
  the sequential double-rounded rank-1 accumulation over k (multiply,
  round, add, round — no fma): numpy's ``einsum`` with
  ``optimize=False`` computes exactly that order in C, and the jnp
  flavor reproduces it with a ``fori_loop`` of adds over rank-1
  products materialized OUTSIDE the loop (the while-loop boundary is
  what stops XLA contracting the multiply into the adds; an
  ``optimization_barrier`` does not).  Single-column weights are
  padded to 8 columns internally — einsum switches to a SIMD dot
  reduction at width 1 — and the result sliced back.

Safe outside this module (verified exact / bit-identical np vs XLA CPU):
plain mul, div, add, sub, min/max/clip, comparisons, ``where``,
integer ops, and a bias add on a ``matmul`` result (the add meets a
loop output, not a multiply).  NOT safe: any other ``mul`` whose
result feeds an ``add``/``sub`` on the traced side — route it through
``fmadd`` or reformulate (e.g. the GRU blend ``(1-z)*h + z*c`` becomes
the single-multiply ``h + z*(c-h)``) — and any ``@`` / ``jnp.dot``.
"""
from __future__ import annotations

import threading

import numpy as np

_LOG2E = np.float32(1.44269504088896341)
# Cody-Waite split of ln2 (Cephes expf): ln2 ~= LN2_HI + LN2_LO
_LN2_HI = np.float32(0.693359375)
_LN2_LO = np.float32(-2.12194440e-4)
# Cephes expf minimax polynomial on [-0.5 ln2, 0.5 ln2]
_EXP_POLY = tuple(np.float32(c) for c in (
    1.9875691500e-4, 1.3981999507e-3, 8.3334519073e-3,
    4.1665795894e-2, 1.6666665459e-1, 5.0000001201e-1))
# clip keeps 2^k a normal f32 (k in [-126, 127]) and the final scale
# exact; sigmoid's tighter clamp is what the tracker actually relies on
_EXP_LO = np.float32(-87.0)
_EXP_HI = np.float32(88.0)
_SIG_CLAMP = np.float32(30.0)
_ONE = np.float32(1.0)
_TWO = np.float32(2.0)
_HALF = np.float32(0.5)

LOG1P_TABLE_SIZE = 4096
LOG1P_TABLE = np.log1p(
    np.arange(LOG1P_TABLE_SIZE, dtype=np.float64)).astype(np.float32)


# ---------------------------------------------------------------------------
# numpy flavor (host)
# ---------------------------------------------------------------------------

class _HostCounts(threading.local):
    """Per-thread work counters of the host twins (read as deltas by
    the caller that owns the thread's work, ``RecurrentTracker``)."""
    fma_ties = 0        # elements ``np_fmadd`` sent to the exact path


COUNTS = _HostCounts()

# f64 bits of an f32 rounding midpoint in the f32-normal range: the
# 24-bit significand plus a half (bit 28 set), nothing below it
_MID_MASK = np.int64(0x1FFFFFFF)
_MID_BITS = np.int64(0x10000000)
_F32_TINY = np.float64(2.0 ** -126)


def _np_fmadd_exact(a, b, c) -> np.ndarray:
    """Exact f32 fma(a, b, c).  f64 holds the 24x24-bit product exactly;
    TwoSum recovers the residual of the f64 add, and round-to-odd on the
    f64 intermediate makes the final f32 cast single-rounded."""
    a64 = np.asarray(a, np.float64)
    b64 = np.asarray(b, np.float64)
    c64 = np.asarray(c, np.float64)
    p = a64 * b64                       # exact
    s = p + c64
    bv = s - p
    err = (p - (s - bv)) + (c64 - bv)   # exact: s + err == p + c
    s = np.ascontiguousarray(np.broadcast_to(s, err.shape))
    bits = s.view(np.int64)
    fix = (err != 0) & ((bits & 1) == 0) & np.isfinite(s)
    dirn = np.where(err > 0, np.float64(np.inf), np.float64(-np.inf))
    s = np.where(fix, np.nextafter(s, dirn), s)
    return s.astype(np.float32)


def np_fmadd(a, b, c) -> np.ndarray:
    """Exact f32 fma(a, b, c) — bit-identical to XLA CPU's contracted
    ``a * b + c`` and to ``_np_fmadd_exact`` on every input.

    The f64 sum ``s`` of the exact product and ``c`` is rounded once
    more to f32.  That second rounding can differ from a single
    rounding of the exact sum only where ``s`` lies exactly on an f32
    rounding midpoint, so only those elements go through the exact
    path: the normal-range midpoint bit pattern, and every nonzero
    ``s`` below the f32-normal range, where the pattern differs.
    Zeros, infinities and NaNs need no correction (the exact path
    leaves a zero or non-finite ``s`` as it is)."""
    s = np.asarray(np.add(np.multiply(a, b, dtype=np.float64), c,
                          dtype=np.float64))
    out = s.astype(np.float32)
    mag = np.abs(s)
    slow = (s.view(np.int64) & _MID_MASK) == _MID_BITS
    slow |= (mag < _F32_TINY) & (mag != 0)
    n_slow = np.count_nonzero(slow)
    if n_slow:
        ba, bb, bc = np.broadcast_arrays(a, b, c)
        out[slow] = _np_fmadd_exact(ba[slow], bb[slow], bc[slow])
        COUNTS.fma_ties += int(n_slow)
    return out


def _np_pow2(k: np.ndarray) -> np.ndarray:
    ki = k.astype(np.int32)
    return np.ascontiguousarray((ki + np.int32(127)) << np.int32(23)) \
        .view(np.float32)


def np_exp(x: np.ndarray) -> np.ndarray:
    x = np.clip(np.asarray(x, np.float32), _EXP_LO, _EXP_HI)
    k = np.floor(np_fmadd(x, _LOG2E, _HALF))
    r = np_fmadd(k, -_LN2_HI, x)
    r = np_fmadd(k, -_LN2_LO, r)
    p = np_fmadd(_EXP_POLY[0], r, _EXP_POLY[1])
    for c in _EXP_POLY[2:]:
        p = np_fmadd(p, r, c)
    s = np_fmadd(p, r * r, r) + _ONE
    return (s * _np_pow2(k)).astype(np.float32)


def np_sigmoid(x: np.ndarray) -> np.ndarray:
    x = np.clip(np.asarray(x, np.float32), -_SIG_CLAMP, _SIG_CLAMP)
    return _ONE / (_ONE + np_exp(-x))


def np_tanh(x: np.ndarray) -> np.ndarray:
    return _TWO * np_sigmoid(_TWO * np.asarray(x, np.float32)) - _ONE


def np_log1p_int(te: np.ndarray) -> np.ndarray:
    """log1p of integer-valued nonnegative f32 (frame gaps)."""
    idx = np.clip(np.asarray(te).astype(np.int32), 0,
                  LOG1P_TABLE_SIZE - 1)
    return LOG1P_TABLE[idx]


def np_matmul(a: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(n, k) @ (k, m) with the pinned sequential-over-k accumulation
    (double rounding per term, ascending k) — bit-identical to
    ``jx_matmul``.  NOT BLAS: ``einsum(optimize=False)`` runs the naive
    C loops in exactly that order."""
    a = np.asarray(a, np.float32)
    w = np.asarray(w, np.float32)
    if w.shape[1] == 1:
        wp = np.zeros((w.shape[0], 8), np.float32)
        wp[:, :1] = w
        return np.einsum("ik,kh->ih", a, wp, optimize=False)[:, :1]
    return np.einsum("ik,kh->ih", a, w, optimize=False)


# ---------------------------------------------------------------------------
# jnp flavor (jit / pallas bodies) — same algorithms, traced ops
# ---------------------------------------------------------------------------

def jx_fmadd(a, b, c):
    # XLA CPU contracts this into one fma; keep it the ONLY
    # mul-feeding-add pattern on the traced side
    return a * b + c


def _jx_pow2(k):
    import jax
    import jax.numpy as jnp
    ki = k.astype(jnp.int32)
    return jax.lax.bitcast_convert_type((ki + 127) << 23, jnp.float32)


def jx_exp(x):
    import jax.numpy as jnp
    x = jnp.clip(x.astype(jnp.float32), _EXP_LO, _EXP_HI)
    k = jnp.floor(jx_fmadd(x, _LOG2E, _HALF))
    r = jx_fmadd(k, -_LN2_HI, x)
    r = jx_fmadd(k, -_LN2_LO, r)
    p = jx_fmadd(_EXP_POLY[0], r, _EXP_POLY[1])
    for c in _EXP_POLY[2:]:
        p = jx_fmadd(p, r, c)
    s = jx_fmadd(p, r * r, r) + _ONE
    return s * _jx_pow2(k)


def jx_sigmoid(x):
    import jax.numpy as jnp
    x = jnp.clip(x.astype(jnp.float32), -_SIG_CLAMP, _SIG_CLAMP)
    return _ONE / (_ONE + jx_exp(-x))


def jx_tanh(x):
    return _TWO * jx_sigmoid(_TWO * x) - _ONE


def jx_matmul(a, w):
    """Traced twin of ``np_matmul``: rank-1 products for every k are
    materialized in ONE multiply, then a ``fori_loop`` accumulates them
    in ascending k.  The loop boundary keeps the multiply and the adds
    in separate computations, so XLA cannot contract them into fmas
    (which would skip the per-term product rounding einsum performs)."""
    import jax
    import jax.numpy as jnp
    if w.shape[1] == 1:
        return jx_matmul(a, jnp.pad(w, ((0, 0), (0, 7))))[:, :1]
    prods = a.T[:, :, None] * w[:, None, :]          # (k, n, m)
    def body(kk, acc):
        return acc + jax.lax.dynamic_index_in_dim(prods, kk, 0,
                                                  keepdims=False)
    return jax.lax.fori_loop(
        0, a.shape[1], body,
        jnp.zeros((a.shape[0], w.shape[1]), jnp.float32))


def jx_log1p_int(te, table=None):
    """Traced twin of ``np_log1p_int``.  Pallas kernel bodies must pass
    the table in as a loaded ref value; plain jit contexts may omit it
    (the module constant is embedded)."""
    import jax.numpy as jnp
    if table is None:
        table = LOG1P_TABLE
    idx = jnp.clip(te.astype(jnp.int32), 0, LOG1P_TABLE_SIZE - 1)
    return jnp.take(jnp.asarray(table), idx, axis=0)
