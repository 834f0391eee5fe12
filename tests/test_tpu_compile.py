"""Compile the TPU path at the paper's widths for a described v5e chip.

Nothing here runs: each test lowers and compiles, for one chip of a
``v5e:2x2`` topology described (not attached) inside a fixture, exactly
what ``MULTISCOPE_PIPELINE`` dispatches on a TPU — 960x544 frames in
16-frame chunks, the 416x256 proxy with 32 px cells and 64 channels, the
tracker at embed 32 / GRU 64 with 64..128 slots.  The public ops are
traced with ``repro.kernels.on_tpu`` patched to True, so they take their
TPU branch; a kernel that runs as Pallas must show ``tpu_custom_call``
in the compiled HLO, which catches a silent reroute to XLA.  A kernel
whose ops module names ``TPU_PALLAS = False`` must show none.

The topology is described only inside the ``topo`` fixture (never at
import), and it skips where the TPU compiler cannot be loaded.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.multiscope import MULTISCOPE_PIPELINE as CFG

CHUNK = 16                                   # executor.DEFAULT_CHUNK
W, H = CFG.detector.resolutions[0]           # 960 x 544
CELL_PX = 16                                 # pipeline.CELL_PX
PROXY_W, PROXY_H = CFG.proxy.resolutions[0]  # 416 x 256
PROXY_C = 64                                 # proxy feature channels
# the sub-frame window sizes chip_smoke's size set gathers, in px
WINDOWS = ((240, 128), (480, 272))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu(one_chip, monkeypatch):
    """Shape factory on the described chip, with the ops tracing their
    TPU branch.  Trace caches are cleared on both sides so no CPU trace
    is reused here and no TPU trace leaks into later tests."""
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    jax.clear_caches()

    def shape(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    yield shape
    jax.clear_caches()


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_proxy_plan(tpu):
    from repro.kernels.proxy_plan import proxy_plan
    feat = tpu((CHUNK, PROXY_H // 32, PROXY_W // 32, PROXY_C))
    hlo = _hlo(lambda f, w, b, t: proxy_plan(
        f, w, b, t, grid_hw=(H // CELL_PX, W // CELL_PX)),
        feat, tpu((PROXY_C,)), tpu(()), tpu(()))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("B", [1, CHUNK])
def test_proxy_score(tpu, B):
    from repro.kernels.proxy_score import proxy_score
    feat = tpu((B, PROXY_H // 32, PROXY_W // 32, PROXY_C))
    hlo = _hlo(proxy_score, feat, tpu((PROXY_C,)), tpu(()), tpu(()))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("win_w,win_h", WINDOWS)
def test_window_gather_batch(tpu, win_w, win_h):
    from repro.kernels.window_gather import window_gather_batch
    hlo = _hlo(lambda f, t: window_gather_batch(
        f, t, win_h=win_h, win_w=win_w, cell=CELL_PX),
        tpu((CHUNK, H, W, 3)), tpu((CHUNK, 3), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("win_w,win_h", WINDOWS)
def test_window_gather_one_frame(tpu, win_w, win_h):
    from repro.kernels.window_gather import window_gather
    hlo = _hlo(lambda f, t: window_gather(
        f, t, win_h=win_h, win_w=win_w, cell=CELL_PX),
        tpu((H, W, 3)), tpu((CFG.windows.max_windows, 2), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("N", [64, 128])
def test_assign(tpu, K, N):
    from repro.kernels.assign import assign_batch
    assert "tpu_custom_call" in _hlo(assign_batch, tpu((K, N, N)))


def _track_operands(tpu):
    from repro.core.tracker import _host_params, init_tracker
    from repro.kernels.track_step import pack_params
    from repro.kernels.track_step.ops import LOG1P_TABLE_2D
    packed = pack_params(_host_params(init_tracker(CFG.tracker)))
    return (tuple(tpu(p.shape) for p in packed),
            tpu(LOG1P_TABLE_2D.shape))


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("Q", [64, 128])
def test_track_step(tpu, K, Q):
    from repro.kernels.track_step import track_step
    from repro.kernels.track_step.ops import TPU_PALLAS
    params, table = _track_operands(tpu)
    Hd, e = CFG.tracker.rnn_dim, CFG.tracker.embed_dim
    hlo = _hlo(track_step, tpu((K, Q, Hd)), tpu((K, Q, 4)), tpu((K, Q)),
               tpu((K, Q)), tpu((K, Q)), tpu((K, Q, e)), tpu((K, Q, 4)),
               tpu((K, Q)), tpu((1, 1)), params, table)
    assert ("tpu_custom_call" in hlo) == TPU_PALLAS


def test_device_chunk_scan(tpu):
    """``DeviceTracker``'s whole-chunk scan: Q = bucket(64 tracks + 64
    detections) = 128 slots."""
    from repro.core.tracker import _device_chunk_scan
    from repro.kernels.track_step.ops import TPU_PALLAS
    params, table = _track_operands(tpu)
    Q, Hd, e = 128, CFG.tracker.rnn_dim, CFG.tracker.embed_dim
    i32 = jnp.int32
    carry = (tpu((Q, Hd)), tpu((Q, 4)), tpu((Q,)), tpu((Q,), i32),
             tpu((Q,), i32), tpu((Q,), i32), tpu((Q,), i32), tpu((), i32),
             tpu((), i32))
    hlo = _hlo(lambda c, f, x, d, v, t, p, tb: _device_chunk_scan(
        c, f, x, d, v, t, p, tb, max_misses=2,
        max_tracks=CFG.tracker.max_tracks),
        carry, tpu((CHUNK,), i32), tpu((CHUNK, Q, e)),
        tpu((CHUNK, Q, 4)), tpu((CHUNK, Q)), tpu((1, 1)), params, table)
    assert ("tpu_custom_call" in hlo) == TPU_PALLAS


@pytest.mark.parametrize("arch", CFG.detector.archs)
def test_detector_forward(tpu, arch):
    from repro.core.detector import _detect_scores, init_detector
    params = jax.tree.map(lambda a: tpu(a.shape, a.dtype),
                          init_detector(arch))
    hlo = _hlo(lambda p, f: _detect_scores(p, f, arch), params,
               tpu((CHUNK, H, W, 3)))
    assert "convolution" in hlo


@pytest.mark.parametrize("hw", [(H, W), WINDOWS[1][::-1]])
def test_yolov3_forward(tpu, hw):
    """YOLOv3 at darknet's widths on a 16-frame chunk of full frames and
    of 480x272 windows (letterboxed to 480x288 inside the forward)."""
    from repro.core.detector import _detect_scores, fold_params, init_detector
    params = jax.tree.map(lambda a: tpu(a.shape, a.dtype), jax.eval_shape(
        lambda: fold_params(init_detector("yolov3"), "yolov3")))
    hlo = _hlo(lambda p, f: _detect_scores(p, f, "yolov3"), params,
               tpu((CHUNK,) + tuple(hw) + (3,)))
    assert "convolution" in hlo
