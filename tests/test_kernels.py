"""Per-kernel validation: Pallas (interpret=True) and the jnp fallback vs
the pure-jnp oracle, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import _chunked_jnp, flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ops import _jnp_fallback
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ops import _chunked_jnp as ssd_chunked
from repro.kernels.ssd_scan.ops import ssd_scan, ssd_step
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.proxy_score.kernel import proxy_score_pallas
from repro.kernels.proxy_score.ref import proxy_score_ref
from repro.kernels.window_gather.kernel import window_gather_pallas
from repro.kernels.window_gather.ref import window_gather_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,D,causal", [
    (2, 128, 128, 4, 2, 64, True),
    (1, 64, 256, 4, 4, 32, True),
    (2, 128, 128, 8, 2, 64, False),
    (1, 64, 64, 2, 1, 128, True),
])
def test_flash_attention(dtype, B, Sq, Skv, Hq, Hkv, D, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, Sq, Hq, D), dtype)
    k = _rand(ks[1], (B, Skv, Hkv, D), dtype)
    v = _rand(ks[2], (B, Skv, Hkv, D), dtype)
    ref = flash_attention_ref(q, k, v, causal=causal)
    chk = _chunked_jnp(q, k, v, causal=causal, sm_scale=1.0 / D ** 0.5,
                       block_k=64)
    pal = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(chk, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_ragged_noncausal():
    """Whisper-style cross attention: Skv not a block multiple."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (2, 100, 4, 32), jnp.float32)
    k = _rand(ks[1], (2, 75, 2, 32), jnp.float32)
    v = _rand(ks[2], (2, 75, 2, 32), jnp.float32)
    ref = flash_attention_ref(q, k, v, causal=False)
    got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,bk", [
    (2, 256, 8, 2, 64, 64),
    (3, 128, 4, 4, 32, 128),
    (1, 512, 16, 8, 128, 256),
])
def test_decode_attention(dtype, B, S, Hq, Hkv, D, bk):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(ks[0], (B, Hq, D), dtype)
    k = _rand(ks[1], (B, S, Hkv, D), dtype)
    v = _rand(ks[2], (B, S, Hkv, D), dtype)
    kvlen = jax.random.randint(ks[3], (B,), 1, S + 1)
    ref = decode_attention_ref(q, k, v, kvlen)
    fb = _jnp_fallback(q, k, v, kvlen, sm_scale=1.0 / D ** 0.5)
    pal = decode_attention_pallas(q, k, v, kvlen, block_k=bk,
                                  interpret=True)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(fb, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("b,S,H,P,N,Q", [
    (2, 64, 4, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 96, 3, 8, 8, 32),
])
def test_ssd_scan(b, S, H, P, N, Q):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, S, N)) * 0.5
    C = jax.random.normal(ks[4], (b, S, N)) * 0.5
    D = jax.random.normal(ks[5], (H,)) * 0.1
    yr, sr = ssd_scan_ref(x, dt, A, B, C, D)
    yc, sc = ssd_chunked(x, dt, A, B, C, D, Q)
    yp, sp = ssd_scan_pallas(x, dt, A, B, C, D, chunk=Q, interpret=True)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), atol=1e-4)


def test_ssd_decode_step_consistency():
    """scan(S) then one ssd_step == scan(S+1) exactly."""
    b, S, H, P, N = 1, 32, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (b, S + 1, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S + 1, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, S + 1, N)) * 0.5
    C = jax.random.normal(ks[4], (b, S + 1, N)) * 0.5
    D = jax.random.normal(ks[5], (H,)) * 0.1
    y_full, s_full = ssd_scan_ref(x, dt, A, B, C, D)
    _, s_pre = ssd_scan_ref(x[:, :S], dt[:, :S], A, B[:, :S], C[:, :S], D)
    y1, s1 = ssd_step(s_pre, x[:, S], dt[:, S], A, B[:, S], C[:, S], D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_full[:, S]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s_full),
                               atol=1e-5)


def test_ssd_non_multiple_padding():
    """ssd_scan pads S to a chunk multiple exactly (dt=0 padding)."""
    b, S, H, P, N = 1, 25, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, S, N)) * 0.5
    C = jax.random.normal(ks[4], (b, S, N)) * 0.5
    D = jax.random.normal(ks[5], (H,)) * 0.1
    yr, sr = ssd_scan_ref(x, dt, A, B, C, D)
    yo, so = ssd_scan(x, dt, A, B, C, D, chunk=16)
    np.testing.assert_allclose(np.asarray(yo), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(so), np.asarray(sr), atol=1e-4)


@pytest.mark.parametrize("B,Hc,Wc,C", [(2, 7, 13, 32), (1, 4, 4, 16),
                                       (3, 8, 8, 64)])
def test_proxy_score(B, Hc, Wc, C):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    feat = jax.random.normal(ks[0], (B, Hc, Wc, C))
    w = jax.random.normal(ks[1], (C,))
    sr, pr = proxy_score_ref(feat, w, 0.3, 0.5)
    sp, pp = proxy_score_pallas(feat, w, 0.3, 0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), atol=1e-6)
    assert (np.asarray(pp) == np.asarray(pr)).all()


@pytest.mark.parametrize("wh,ww", [(64, 96), (32, 32), (96, 64)])
def test_window_gather(wh, ww):
    frame = jax.random.normal(jax.random.PRNGKey(7), (160, 256, 3))
    oc = jnp.array([[0, 0], [1, 2], [2, 3]], jnp.int32)
    max_cy = (160 - wh) // 32
    max_cx = (256 - ww) // 32
    oc = jnp.minimum(oc, jnp.array([max_cy, max_cx]))
    ref = window_gather_ref(frame, oc * 32, win_h=wh, win_w=ww)
    pal = window_gather_pallas(frame, oc, win_h=wh, win_w=ww,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref))
    # numpy-crop oracle: the kernel must be a pure copy of the slices
    f = np.asarray(frame)
    for k, (cy, cx) in enumerate(np.asarray(oc)):
        crop = f[cy * 32:cy * 32 + wh, cx * 32:cx * 32 + ww]
        np.testing.assert_array_equal(np.asarray(pal)[k], crop)


@pytest.mark.parametrize("wh,ww", [(64, 96), (32, 32), (96, 64)])
def test_window_gather_batch(wh, ww):
    """Cross-frame gather (the chunked engine's hot path): Pallas
    interpret=True vs the jnp oracle vs direct numpy crops."""
    from repro.kernels.window_gather.kernel import (
        window_gather_batch_pallas)
    from repro.kernels.window_gather.ref import window_gather_batch_ref
    frames = jax.random.normal(jax.random.PRNGKey(8), (3, 160, 256, 3))
    tbl = jnp.array([[0, 0, 0], [2, 1, 2], [1, 2, 3], [2, 0, 1]],
                    jnp.int32)
    max_cy = (160 - wh) // 32
    max_cx = (256 - ww) // 32
    tbl = jnp.minimum(tbl, jnp.array([2, max_cy, max_cx]))
    ref = window_gather_batch_ref(
        frames, tbl * jnp.array([1, 32, 32], jnp.int32),
        win_h=wh, win_w=ww)
    pal = window_gather_batch_pallas(frames, tbl, win_h=wh, win_w=ww,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref))
    f = np.asarray(frames)
    for k, (b, cy, cx) in enumerate(np.asarray(tbl)):
        crop = f[b, cy * 32:cy * 32 + wh, cx * 32:cx * 32 + ww]
        np.testing.assert_array_equal(np.asarray(pal)[k], crop)


@pytest.mark.parametrize("B,hp,wp,C,hc,wc", [
    (2, 20, 32, 16, 5, 8),     # clean downscale
    (1, 18, 30, 32, 5, 8),     # ragged spans
    (3, 6, 8, 16, 9, 11),      # upscale (hc > hp)
    (2, 12, 12, 8, 12, 12),    # identity mapping
])
def test_proxy_plan(B, hp, wp, C, hc, wc):
    """Fused plan kernel: Pallas interpret=True vs jnp ref vs the host
    map_proxy_grid path — mapped grids must be BIT-identical (the plan
    fast paths depend on it), stats must match a direct reduction."""
    from repro.core.pipeline import map_proxy_grid
    from repro.kernels.proxy_plan.kernel import proxy_plan_pallas
    from repro.kernels.proxy_plan.ops import span_matrix
    from repro.kernels.proxy_plan.ref import proxy_plan_ref
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    feat = jax.random.normal(ks[0], (B, hp, wp, C))
    w = jax.random.normal(ks[1], (C,)) * 0.5
    b, th = 0.1, 0.5
    sy = jnp.asarray(span_matrix(hc, hp))
    sx = jnp.asarray(span_matrix(wc, wp))
    gr, sr = proxy_plan_ref(feat, w, b, th, sy, sx)
    gp, sp = proxy_plan_pallas(feat, w, b, th, sy, sx, interpret=True)
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(gr))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(sr))
    # host oracle: threshold the scores on the host, map with the
    # integral-image path, reduce with numpy
    logits = np.einsum("bhwc,c->bhw", np.asarray(feat, np.float64),
                       np.asarray(w, np.float64)) + b
    pos = (1.0 / (1.0 + np.exp(-logits)) > th).astype(np.int8)
    for k in range(B):
        host = map_proxy_grid(pos[k], (wc, hc))
        got = np.asarray(gr[k])
        np.testing.assert_array_equal(got, host.astype(np.int8))
        cnt = int(host.sum())
        assert int(sr[k, 0]) == cnt
        if cnt:
            ys, xs = np.nonzero(host)
            assert tuple(np.asarray(sr[k, 1:5])) == (
                ys.min(), ys.max(), xs.min(), xs.max())
        else:
            assert tuple(np.asarray(sr[k, 1:5])) == (hc, -1, wc, -1)


@pytest.mark.parametrize("K,N", [(1, 1), (3, 4), (2, 9), (4, 16)])
def test_assign(K, N):
    """Batched JV: Pallas interpret=True vs the vmapped-jnp fallback vs
    the host _hungarian_np oracle.  Costs are quantized to multiples of
    1/64 so f32 potential arithmetic is exact and even the first-index
    tie-breaking must agree across all three."""
    from repro.kernels.assign.kernel import assign_pallas
    from repro.kernels.assign.ops import _solve_vmapped
    from repro.kernels.assign.ref import assign_ref
    rng = np.random.default_rng(10 * K + N)
    costs = rng.integers(0, 256, (K, N, N)).astype(np.float32) / 64.0
    ref = assign_ref(costs)
    fb = np.asarray(_solve_vmapped(jnp.asarray(costs)))
    pal = np.asarray(assign_pallas(jnp.asarray(costs), interpret=True))
    np.testing.assert_array_equal(fb, ref)
    np.testing.assert_array_equal(pal, ref)
    # each row a permutation with minimal total (vs scipy when present)
    scipy_opt = pytest.importorskip("scipy.optimize")
    for k in range(K):
        assert sorted(ref[k]) == list(range(N))
        r, c = scipy_opt.linear_sum_assignment(costs[k])
        np.testing.assert_allclose(
            costs[k][np.arange(N), ref[k]].sum(), costs[k][r, c].sum())


def _track_step_operands(rng, K, Q, H, e, M):
    """Random track-step operands honoring the slot contract: live
    tracks and valid detections are PREFIXES, te gaps are integers,
    boxes live in roughly world units."""
    def g(*s):
        return rng.standard_normal(s).astype(np.float32)

    params = {
        "det_proj/w": g(e + 6, e) * 0.5, "det_proj/b": g(e) * 0.1,
        "gru/wz": g(e + H, H) * 0.5, "gru/wr": g(e + H, H) * 0.5,
        "gru/wh": g(e + H, H) * 0.5,
        "gru/bz": g(H) * 0.1, "gru/br": g(H) * 0.1, "gru/bh": g(H) * 0.1,
        "match/w0": g(H + e + 6, M) * 0.5, "match/b0": g(M) * 0.1,
        "match/w1": g(M, 1) * 0.5, "match/b1": g(1) * 0.1,
    }
    h_r = np.zeros((K, Q, H), np.float32)
    tbox_r = np.zeros((K, Q, 4), np.float32)
    alive_r = np.zeros((K, Q), np.float32)
    te_gap_r = np.zeros((K, Q), np.float32)
    te_match = np.zeros((K, Q), np.float32)
    x = np.zeros((K, Q, e), np.float32)
    dbox = np.zeros((K, Q, 4), np.float32)
    dvalid = np.zeros((K, Q), np.float32)
    for k in range(K):
        T = int(rng.integers(0, Q + 1))
        n = int(rng.integers(0, Q + 1))
        h_r[k, :T] = g(T, H) * 0.5
        tbox_r[k, :T] = rng.random((T, 4), np.float32)
        alive_r[k, :T] = 1.0
        te_gap_r[k, :T] = rng.integers(1, 9, T)
        te_match[k] = float(rng.integers(0, 9))
        x[k, :n] = g(n, e) * 0.5
        dbox[k, :n] = rng.random((n, 4), np.float32)
        dvalid[k, :n] = 1.0
    thr = np.full((1, 1), 0.35, np.float32)
    return (h_r, tbox_r, alive_r, te_gap_r, te_match, x, dbox,
            dvalid), thr, params


@pytest.mark.parametrize("K,Q,H,e,M", [(1, 8, 16, 8, 16),
                                       (2, 16, 24, 16, 24),
                                       (3, 8, 20, 12, 20)])
def test_track_step(K, Q, H, e, M):
    """Fused tracker step: Pallas interpret=True vs the vmapped-jnp
    fallback vs the numpy oracle, BIT-exact (the fastmath contract),
    plus the matched-column semantics (unique real columns, forbidden
    pairs reported -1)."""
    from repro.kernels.track_step import pack_params, track_step_ref
    from repro.kernels.track_step.kernel import track_step_pallas
    from repro.kernels.track_step.ops import LOG1P_TABLE_2D, _step_vmapped
    rng = np.random.default_rng(1000 * K + Q + H + e)
    arrs, thr, np_params = _track_step_operands(rng, K, Q, H, e, M)
    packed = pack_params(np_params)
    ref = track_step_ref(*arrs, thr, packed, LOG1P_TABLE_2D)
    fb = _step_vmapped(*[jnp.asarray(a) for a in arrs],
                       jnp.asarray(thr), *packed, LOG1P_TABLE_2D[:, 0])
    pal = track_step_pallas(*[jnp.asarray(a) for a in arrs],
                            jnp.asarray(thr), packed, LOG1P_TABLE_2D,
                            interpret=True)
    for r, f, p in zip(ref, fb, pal):
        np.testing.assert_array_equal(np.asarray(f), r)
        np.testing.assert_array_equal(np.asarray(p), r)
    matched = ref[0]
    alive, dvalid = arrs[2], arrs[7]
    for k in range(K):
        cols = matched[k][matched[k] >= 0]
        assert len(set(cols.tolist())) == len(cols)       # no col reuse
        assert np.all(dvalid[k][cols] > 0)                # real dets only
        assert np.all(matched[k][alive[k] <= 0] == -1)    # dead rows
