"""The trace reduction, on a small trace recorded on a TPU v5e.

``bench/tests/data/small_tpu.xplane.pb`` was recorded by
``bench/tools/record_test_trace.py``: three rounds of the detector on
4 full frames, a window gather and the detector on its crops, each
round followed by a 20 ms host sleep.  ``small_tpu.json`` holds what
the host clock saw.
"""
from __future__ import annotations

import json
import os

import pytest

import bench_testkit as kit  # noqa: F401  (puts the repo on sys.path)

from bench.lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small_tpu.json")) as f:
        meta = json.load(f)
    return trace.reduce(XPLANE, (), None, 1), meta


def test_window_and_idle_share(recorded):
    red, meta = recorded
    # the annotation spans the three rounds and their sleeps
    assert red["window_s"] == pytest.approx(meta["window_perf_s"], rel=0.05)
    assert 0 < red["busy_s"] < red["window_s"]
    # three 20 ms sleeps leave the device idle at least that long
    assert red["window_s"] - red["busy_s"] >= 0.9 * 3 * meta["sleep_s"]
    idle = [s for _, s in red["idle_gaps"]]
    assert idle == sorted(idle, reverse=True) and len(idle) <= 10
    assert idle[0] >= 0.9 * meta["sleep_s"]


def test_detector_device_time(recorded):
    red, meta = recorded
    det = red["programs"].get("jit__detect_scores", 0.0)
    # two detector calls a round, on the device for part of each round
    assert 0 < det <= sum(meta["host_work_s"])
    assert det <= red["busy_s"] + 1e-9
    ops = dict(red["device_ops"])
    assert len(red["device_ops"]) <= 10
    assert sum(ops.values()) <= red["busy_s"] * 1.0001 + 1e-9 or \
        len(ops) == 10


def test_reduction_matches_the_recorded_numbers(recorded):
    red, meta = recorded
    want = meta["reduced"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["programs"] == pytest.approx(want["programs"])
