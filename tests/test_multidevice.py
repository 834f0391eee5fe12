"""Multi-device chunk round-robin under a forced 4-device host platform.

The executor's device-assignment path (``_RunContext.device_for`` +
per-chunk ``jax.device_put``) was previously exercised only at world
size 1.  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` must be
set before jax initializes, so the scenario runs in a subprocess with a
clean interpreter: it asserts 4 devices are visible, that chunks
actually round-robin ALL of them (sub-frame window gathers force the
padded device buffers into use), and that tracks match the per-frame
reference under both schedulers.

Note on tolerance: forced host-platform devices PARTITION XLA's
intra-op threadpool, so a convolution dispatched to device 2 may split
its reductions differently than the same convolution on device 0 —
last-ulp differences in box coordinates between devices are expected
(bit-identity holds per device; world-size-1 CI keeps asserting it
exactly).  Track STRUCTURE (count, frames, ids) and the RunResult
counters must still match exactly; boxes are compared at float32
tolerance.
"""
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os
assert "xla_force_host_platform_device_count=4" in \
    os.environ.get("XLA_FLAGS", "")
import numpy as np
import jax

assert jax.device_count() == 4, jax.devices()

from repro.configs.multiscope import MULTISCOPE_PIPELINE
from repro.core import pipeline as pl
from repro.core.executor import (ClipExecutor, ExecutorOptions,
                                 run_clip_streamed)
from repro.core.proxy import ProxyModel
from repro.core.tracker import init_tracker
from repro.core.train_models import train_detector
from repro.data.video_synth import make_split

cfg = MULTISCOPE_PIPELINE.reduced()
clips = make_split("caldot1", "train", 1, n_frames=16)
det, _ = train_detector("ssd-lite", clips,
                        [cfg.detector.resolutions[-1]], steps=40)
bank = pl.ModelBank(cfg, {"ssd-lite": det, "ssd-deep": det})
res = cfg.proxy.resolutions[-1]
proxy = ProxyModel(cfg.proxy.cell, cfg.proxy.base_channels, res)
bank.proxies = {res: proxy}
bank.sizes_cells = [pl.det_grid(cfg.detector.resolutions[-1]),
                    (3, 2), (5, 3)]
bank.ref_grid = pl.det_grid(cfg.detector.resolutions[-1])
bank.tracker_params = init_tracker(cfg.tracker)
W, H = cfg.detector.resolutions[-1]
frame, _ = pl.render_frame(clips[0], 0, W, H)
s, _ = proxy.scores(pl._downsample(frame, res))
# sparse positive grid -> real sub-frame windows -> device uploads
params = pl.PipelineParams(
    "ssd-lite", cfg.detector.resolutions[-1], 0.4, gap=1,
    proxy_res=res, proxy_threshold=float(np.quantile(s, 0.85)),
    tracker="sort", refine=False, chunk_size=4)

clip = clips[0]
ref = pl.run_clip_frames(bank, params, clip)

# the default device list is all 4 forced host devices, and the 4
# chunks of a 16-frame clip at B=4 round-robin every one of them
ex = ClipExecutor(bank, params, ExecutorOptions(prefetch=False))
run = ex.start(clip)
assert len(run.ctx.devices) == 4, run.ctx.devices
tasks = ex._tasks(run.ctx)
assert len(tasks) == 4
assigned = {run.ctx.device_for(t).id for t in tasks}
assert assigned == {0, 1, 2, 3}, assigned
seq = ex.finish(run)

stream = run_clip_streamed(bank, params, clip,
                           ExecutorOptions(decode_workers=2))

for r in (seq, stream):
    assert r.frames_processed == ref.frames_processed
    assert r.detector_windows == ref.detector_windows
    assert r.full_frames == ref.full_frames
    assert r.skipped_frames == ref.skipped_frames
    assert len(r.tracks) == len(ref.tracks)
    for a, b in zip(ref.tracks, r.tracks):
        # structure exact; boxes to fp32 tolerance (cross-device
        # reduction-order divergence, see module docstring)
        assert a.shape == b.shape
        np.testing.assert_array_equal(a[:, 0], b[:, 0])   # frames
        np.testing.assert_array_equal(a[:, 5], b[:, 5])   # track ids
        np.testing.assert_allclose(a[:, 1:5], b[:, 1:5],
                                   rtol=0, atol=1e-6)

# a per-clip device offset rotates the assignment (run_clips' stagger)
run2 = ex.start(clip, device_offset=1)
assert run2.ctx.device_for(tasks[0]).id == 1
ex.cancel(run2)
print("MULTIDEVICE-OK")
"""


def test_chunk_round_robin_across_four_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # forced host devices are CPU devices: never reach for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=540)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "MULTIDEVICE-OK" in proc.stdout
