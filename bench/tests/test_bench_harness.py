"""CPU tests of the on-chip benchmark's harness (no chip is touched)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_testkit as kit

from bench.lib import registry, result


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A root with the tiny batch cell, and its models."""
    tmp = str(tmp_path_factory.mktemp("tiny"))
    root = kit.make_root(tmp)
    cache = os.path.join(tmp, "models")
    kit.tiny_models(cache, "caldot1")
    return root, cache


def run_tiny(tiny, cell_name, monkeypatch, trace=False, seed=7):
    from bench import run
    from bench.lib import models
    root, cache = tiny
    monkeypatch.setattr(models, "CACHE", cache)
    cell = registry.find_cell(cell_name, root=root)
    obj, checks = run.run_cell(cell, seed, 1.0, trace,
                               chip_check=lambda chips: None)
    return cell, obj, checks


# -- finding parts by name --------------------------------------------------

def test_every_cell_resolves_by_name():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        cell = registry.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        registry.find_entry(cell.entry)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert hasattr(registry.find_metric(m["name"]), "read")
        assert os.path.exists(os.path.join(
            registry.BENCH_DIR, "limits", f"{w['name']}.json"))


def test_temporary_cell_from_files_alone(tmp_path):
    root = kit.make_root(str(tmp_path))
    cell = registry.find_cell("tiny.batch", root=root)
    assert cell.config["name"] == "tiny"
    assert cell.traffic["clip_frames"] == kit.TINY_BATCH["clip_frames"]
    assert cell.entry == "batch"
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s",
                                                     "setup_s"}
    assert "detect_roofline.batch" in {m["name"] for m in cell.per_layer}
    with pytest.raises(KeyError):
        registry.find_cell("no.such.cell", root=root)


def test_unknown_device_kind_is_an_error():
    assert registry.peaks("TPU v5 lite")["flops_per_s"] == 1.97e14
    with pytest.raises(KeyError):
        registry.peaks("TPU v99")


# -- the detection gap's statistic -------------------------------------------

def test_det_gap_quantile_reads_past_one_near_tie():
    """The compared quantile is a reading of one gap; a single near-tie
    among hundreds moves the largest gap, not the 99th percentile, and
    a shift of a tenth of the detections moves both."""
    from bench.reference.compare import DET_QUANTILE, gap_quantile
    gaps = list(np.linspace(0.0, 0.2, 500))
    base = gap_quantile(gaps, DET_QUANTILE)
    assert base in gaps and base <= 0.2
    assert gap_quantile(gaps + [3.0], DET_QUANTILE) == pytest.approx(
        base, abs=1e-3)
    assert gap_quantile(gaps + [3.0], 1.0) == 3.0
    assert gap_quantile(gaps + [3.0] * 50, DET_QUANTILE) == 3.0
    assert gap_quantile([], DET_QUANTILE) == 0.0


# -- the result line --------------------------------------------------------

def _good_line():
    checks = [("det_gap_p99", 0.001, 0.01)]
    return result.line(True, 3, 0, {"frames_per_s": result.metric(
        12.5, "frames/s")}, {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1, "memory_peak_bytes": 10},
        checks), checks


def test_result_line_schema(capsys):
    obj, checks = _good_line()
    result.emit(obj, checks)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[:5] == list(result.KEYS)
    assert list(last)[-1] == "checks"
    assert last["checks"]["det_gap_p99"] == {"value": 0.001,
                                             "limit": 0.01}
    assert err.strip().splitlines()[-1].startswith("check det_gap_p99")
    bad = dict(obj)
    del bad["device"]
    with pytest.raises(ValueError):
        result.validate(bad)


def test_refuses_to_run_without_a_chip():
    """On the CPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(kit.ROOT, "bench", "run.py"),
         "--workload", "accurate.caldot1", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=kit.ROOT, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


# -- a whole run on the CPU --------------------------------------------------

def test_batch_cell_runs_correct_on_cpu(tiny, monkeypatch):
    cell, obj, checks = run_tiny(tiny, "tiny.batch", monkeypatch)
    result.validate(obj)
    assert obj["correct"], checks
    assert obj["metrics"]["frames_per_s"]["value"] > 0
    assert set(obj["metrics"]) == {"frames_per_s", "setup_s"}
    assert obj["attempted"] >= 2


def test_traced_run_on_cpu_with_a_recorded_trace(tiny, monkeypatch):
    """The whole ``--trace 1`` path: the spans window, the profiled
    window (its profile replaced by the small recorded TPU trace), both
    checked, every per-layer metric of the cell in the line."""
    import time
    from contextlib import contextmanager
    from bench.lib import trace as tr
    recorded = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "small_tpu.xplane.pb")

    @contextmanager
    def capture(out_dir, box):
        box["perf_t0"] = time.perf_counter_ns()
        yield box
        box["xplane"] = recorded
    monkeypatch.setattr(tr, "capture", capture)
    monkeypatch.setattr(tr, "warm_profiler", lambda out_dir: None)
    info = result.device_info
    monkeypatch.setattr(result, "device_info", lambda chips: dict(
        info(chips), kind="TPU v5 lite"))
    cell, obj, checks = run_tiny(tiny, "tiny.batch", monkeypatch,
                                 trace=True, seed=19)
    result.validate(obj)
    assert obj["correct"], checks
    assert set(obj["metrics"]) == {m["name"] for m in cell.per_layer}
    assert obj["device"]["busy_s"] > 0 and obj["device"]["window_s"] > 0
    assert obj["breakdown"]["device_ops"]
    assert obj["attempted"] >= 2 * len(cell.traffic["clip_ids"])


# -- faults planted in the timed path must make `correct` false -----------

def _tracker_state_unchanged(monkeypatch):
    """Every tracker step returns with its state as it found it."""
    import copy
    from repro.core.tracker import RecurrentTracker
    step = RecurrentTracker.step

    def frozen(self, *a, **kw):
        keep = copy.deepcopy((self.active, self.finished, self._next_id))
        step(self, *a, **kw)
        self.active, self.finished, self._next_id = keep
    monkeypatch.setattr(RecurrentTracker, "step", frozen)


def _half_batch_left_out(monkeypatch):
    """The detector decodes only the first half of each batch."""
    from repro.core.detector import Detector
    detect = Detector.detect_batch

    def half(self, frames, conf, origins=None, scales=None, max_dets=64,
             n_valid=None):
        out = detect(self, frames, conf, origins=origins, scales=scales,
                     max_dets=max_dets, n_valid=n_valid)
        keep = (len(out) + 1) // 2
        return out[:keep] + [np.zeros((0, 5), np.float32)] * (
            len(out) - keep)
    monkeypatch.setattr(Detector, "detect_batch", half)


def _answer_altered(monkeypatch):
    """Each detection's centre is moved right by 2% of the frame where
    the detector decodes it."""
    from repro.core import detector
    decode = detector.decode_detections

    def moved(*a, **kw):
        d = decode(*a, **kw).copy()
        d[:, 0] += 0.02
        return d
    monkeypatch.setattr(detector, "decode_detections", moved)


@pytest.mark.parametrize("fault", [_tracker_state_unchanged,
                                   _half_batch_left_out, _answer_altered])
def test_planted_fault_makes_correct_false(tiny, monkeypatch, fault):
    fault(monkeypatch)
    cell, obj, checks = run_tiny(tiny, "tiny.batch", monkeypatch, seed=11)
    assert obj["correct"] is False, checks


def test_control_reads_above_the_limits(tiny, monkeypatch):
    """The control (the reference with fp8 convolution operands and
    bfloat16 host operands in the program's place) fails the limits."""
    from bench.lib import models
    from bench.reference.compare import verdict
    root, cache = tiny
    monkeypatch.setattr(models, "CACHE", cache)
    cell = registry.find_cell("tiny.batch", root=root)
    entry = registry.find_entry("batch")
    st = entry.setup(cell, 5, 1.0, lambda *a: None)
    entry.window(st)
    prec = cell.config["precision"]
    got = entry.check(st, control={
        "conv_operands": prec["control_conv_operands"],
        "host_operands": prec["control_host_operands"]})
    ok, _ = verdict(got["program"], kit.LIMITS)
    assert ok
    ok, rows = verdict(got["control"], kit.LIMITS)
    assert not ok, rows


def test_fault_in_a_later_window_makes_correct_false(tiny, monkeypatch):
    """A traced run checks its profiled window too: a fault planted
    only there fails the check of the run."""
    from bench.lib import models
    from bench.reference.compare import verdict
    root, cache = tiny
    monkeypatch.setattr(models, "CACHE", cache)
    cell = registry.find_cell("tiny.batch", root=root)
    entry = registry.find_entry("batch")
    st = entry.setup(cell, 13, 1.0, lambda *a: None)
    entry.window(st)
    assert verdict(entry.check(st)["program"], kit.LIMITS)[0]
    _answer_altered(monkeypatch)
    entry.window(st, 0.1)
    ok, rows = verdict(entry.check(st)["program"], kit.LIMITS)
    assert not ok, rows
    assert entry.attempted(st)[0] == sum(len(r) for _, _, r in st.runs)


def test_stage_metrics_read_from_the_unprofiled_window(tiny, monkeypatch):
    """The stage spans come from a window with no profiler on, and each
    stage's self time per frame reads above 0."""
    from bench import run
    from bench.lib import models
    root, cache = tiny
    monkeypatch.setattr(models, "CACHE", cache)
    cell = registry.find_cell("tiny.batch", root=root)
    entry = registry.find_entry("batch")
    st = entry.setup(cell, 17, 1.0, lambda *a: None)
    spans, counters, e2e = run.spans_window(entry, st)
    assert e2e["frames_per_s"] > 0
    ctx = run.MetricContext(cell, st, entry, spans, counters, None, {},
                            registry.peaks("TPU v5 lite"))
    for stage in ("decode", "proxy", "detect", "track"):
        v = registry.find_metric(f"stage_ms_per_frame.{stage}.batch"
                                 ).read(ctx)
        assert v is not None and v > 0, stage
    for name in ("detect_roofline.batch", "pipeline_mfu.batch",
                 "device_idle.batch"):
        assert registry.find_metric(name).read(ctx) is None, name


# -- operations counted from shapes ------------------------------------------

FAMILIES = sorted(f[:-3] for f in os.listdir(os.path.join(
    registry.BENCH_DIR, "reference", "detectors")) if f.endswith(".py"))
HW = [(64, 96), (35, 50)]


def _xla_flops(fn, p, h, w):
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32)
    ca = jax.jit(fn).lower(p, x).compile().cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


def _detector_configs(family):
    """The detector blocks of every configuration of ``family``: the
    benchmark's configuration files and the test kit's."""
    blocks = [registry.load_json(os.path.join(registry.BENCH_DIR, "configs",
                                              f))["detector"]
              for f in sorted(os.listdir(os.path.join(registry.BENCH_DIR,
                                                      "configs")))]
    blocks.append(kit.TINY_CONFIG["detector"])
    return [d for d in blocks if d["family"] == family]


@pytest.mark.parametrize("hw", HW)
@pytest.mark.parametrize("family", FAMILIES)
def test_flop_counts_against_xla_cost_analysis(family, hw):
    """A family's ``work`` leaves out only the bias and activation, so
    it sits just under XLA's own count of the family's reference
    ``forward`` and never above it."""
    from bench.lib import models
    from bench.reference import nets
    from repro.core.detector import init_detector
    fam = registry.find_family(family)
    h, w = hw
    dets = _detector_configs(family)
    assert dets, f"no configuration uses the family {family!r}"
    for d in dets:
        p = nets.take(models.flatten(init_detector(d["arch"], 0),
                                     "detector"), "detector")
        xla = _xla_flops(lambda p, a: fam.reference.forward(p, a, d), p, h, w)
        mine = fam.program.work(d, h, w)[0]
        assert 0.97 * xla <= mine <= xla, d["arch"]


@pytest.mark.parametrize("hw", HW)
def test_proxy_flop_counts_against_xla_cost_analysis(hw):
    from bench.lib import flops
    from repro.core.proxy import init_proxy, proxy_features
    h, w = hw
    prox = _xla_flops(lambda p, a: proxy_features(p, a, 32),
                      init_proxy(32, 8, 0), h, w)
    mine = flops.conv_flops(flops.proxy_layers(32, 8)[:-1], h, w)
    assert 0.97 * prox <= mine <= prox
