"""``chip_smoke.py``'s phases on the CPU at ``.reduced()`` size.

The script itself refuses to run without a TPU; these tests drive its
phase functions (not its platform check) on 16-frame clips with a
briefly trained detector, so each phase's control flow and its checks
(MOTA within tolerance of the comparison path, a detection, a track of
at least ``MIN_TRACK`` frames, exact query answers) run in tier-1.
"""
import dataclasses
import os
import sys

import pytest

from repro.configs.multiscope import MULTISCOPE_PIPELINE

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke_sys():
    """At 256x160 the proxy's coarse 8x5 cell grid marks most of the
    frame, so the calibrated threshold plans full frames everywhere; a
    higher one makes the planner gather sub-frame windows, as the
    calibrated one does at 960x544."""
    sys_ = chip_smoke.build_system(MULTISCOPE_PIPELINE.reduced(),
                                   n_frames=16, det_steps=150,
                                   proxy_steps=40)
    sys_["params"] = dataclasses.replace(sys_["params"],
                                         proxy_threshold=0.8)
    return sys_


@pytest.fixture(scope="module")
def host_runs(smoke_sys):
    return chip_smoke.phase_extraction(smoke_sys)


def test_extraction_phase(host_runs):
    assert len(host_runs) == 2
    assert max(len(t) for r in host_runs for t in r.tracks) \
        >= chip_smoke.MIN_TRACK


def test_device_track_phase(smoke_sys, host_runs):
    got = chip_smoke.phase_device_track(smoke_sys, host_runs)
    assert all(r.dispatches["track"] > 0 for r in got)


def test_fleet_phase(smoke_sys, host_runs):
    got = chip_smoke.phase_fleet(smoke_sys, host_runs)
    assert len(got) == 4


def test_store_phase(smoke_sys):
    chip_smoke.phase_store(smoke_sys, segment=8)


def test_multichip_phase_on_one_device(smoke_sys):
    chip_smoke.phase_multichip(smoke_sys)


def test_compare_rejects_a_mota_gap(smoke_sys, host_runs):
    """A run whose tracks are gone is more than MOTA_TOL from its
    comparison run, and the check must say so."""
    clips = smoke_sys["clips"][:2]
    empty = [dataclasses.replace(r, tracks=[]) for r in host_runs]
    with pytest.raises(AssertionError, match="MOTA"):
        chip_smoke.compare(empty, host_runs, clips)


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
