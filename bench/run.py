#!/usr/bin/env python3
"""MultiScope on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs``), a traffic mix (``bench/traffic``) and, through the
mix, an entry driver (``bench/entries``).  In one process on the cell's
chips the run

  1. exits with 3, printing no result, without a TPU or with fewer
     chips than the cell asks for;
  2. keeps JAX's persistent compilation cache in ``bench/.cache/jax``
     (through the program's ``core.compile_cache``);
  3. sets up (models, frames, the cell's shapes), then measures for
     ``--seconds``: with ``--trace 0`` the cell's end-to-end metrics,
     with ``--trace 1`` its per-layer metrics: the program's spans
     over the whole window, then a device trace of a shorter profiled
     window (the mix's ``trace_seconds``), both compared;
  4. compares what the window produced with the plain reference
     (``bench/reference``) and prints, as its last line, one JSON object
     with ``correct``, ``attempted``, ``failed``, ``metrics``,
     ``device`` (and ``breakdown`` when traced), the compared numbers
     last under ``checks``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from contextlib import contextmanager

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
JAX_CACHE = os.path.join(HERE, ".cache", "jax")
TRACE_DIR = os.path.join(HERE, ".cache", "trace")


class NoChip(RuntimeError):
    pass


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def require_chip(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devs)}")


class CompileCounter:
    """Lowerings (one per new program, cached or not) and backend
    compiles, counted through jax.monitoring."""

    def __init__(self):
        import jax
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._note)

    def _note(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def limits_for(cell) -> dict:
    """The cell's limits, ``bench/limits/<cell>.json``."""
    from bench.lib.registry import load_json
    return load_json(os.path.join(cell.bench_dir, "limits",
                                  f"{cell.name}.json"))["limits"]


def enable_cache() -> None:
    """The program's persistent compilation cache, in the directory the
    benchmark gives it (``JAX_COMPILATION_CACHE_DIR``), keeping every
    compile however short."""
    import jax
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             chip_check=require_chip, t_start: float = T_START):
    """One run -> (result line, checks).  Raises ``NoChip``."""
    from bench.lib import registry, result
    from bench.lib import trace as tr
    chip_check(cell.chips)
    counter = CompileCounter()
    entry = registry.find_entry(cell.entry)
    limits = limits_for(cell)

    if trace:
        tr.warm_profiler(os.path.join(TRACE_DIR, cell.name + ".warm"))
    st = entry.setup(cell, seed, seconds, log)
    setup_s = time.perf_counter() - t_start
    low0, comp0 = counter.lowered, counter.compiled
    if trace:
        # the stage spans from the whole window with no profiler on; the
        # device metrics from a profiled window of the mix's
        # ``trace_seconds`` (the profiler slows the host's side of the
        # window, and a profile of the whole window outgrows the host)
        spans, counters, e2e = spans_window(entry, st)
        box: dict = {}
        with tracing() as t:
            with tr.capture(os.path.join(TRACE_DIR, cell.name), box):
                entry.window(st, cell.traffic.get("trace_seconds",
                                                  seconds))
            dev_spans = t.snapshot()
        dev_counters = entry.counters(st)
    else:
        e2e = entry.window(st)
    in_window = {"lowered": counter.lowered - low0,
                 "compiled": counter.compiled - comp0}
    device = result.device_info(cell.chips)
    breakdown = None
    if trace:
        red = tr.reduce(box["xplane"], dev_spans, box["perf_t0"], cell.chips)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    import resource
    log(f"setup {entry.__name__}: {getattr(st, 'setup_notes', {})}")
    log(f"window: {entry.counters(st) if not trace else counters} "
        f"compiles_in_window {in_window} host_maxrss_mb "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024:.0f}")

    t_check = time.perf_counter()
    readings = entry.check(st)["program"]       # every window run
    log(f"check_s {time.perf_counter() - t_check:.3f}")
    from bench.reference.compare import verdict
    correct, checks = verdict(readings, limits)
    attempted, failed = entry.attempted(st)

    metrics = {}
    if not trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics["setup_s"] = result.metric(setup_s, units["setup_s"])
        for name, v in e2e.items():
            if name in units:
                metrics[name] = result.metric(v, units[name])
    else:
        ctx = MetricContext(cell, st, entry, spans, counters, red,
                            dev_counters, registry.peaks(device["kind"]))
        for m in cell.per_layer:
            v = registry.find_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = result.metric(v, m["unit"])
        if box.get("xplane"):
            import shutil
            shutil.rmtree(os.path.join(TRACE_DIR, cell.name),
                          ignore_errors=True)
    return result.line(correct, attempted, failed, metrics, device,
                       checks, breakdown), checks


@contextmanager
def tracing():
    """The program's span tracer on for the body."""
    from repro.obs.trace import TRACER
    TRACER.clear()
    TRACER.enable(capacity=1 << 20)
    try:
        yield TRACER
    finally:
        TRACER.disable()


def spans_window(entry, st):
    """The whole window with the program's spans on and no profiler ->
    (spans, the entry's counters, end-to-end metrics)."""
    with tracing() as t:
        e2e = entry.window(st)
        spans = t.snapshot()
    return spans, entry.counters(st), e2e


class MetricContext:
    """What a per-layer metric's reader may read: the spans and counters
    of the unprofiled window (``spans``, ``counters``), and the device
    trace's reduction and counters of the profiled one (``device``,
    ``device_counters``)."""

    def __init__(self, cell, st, entry, spans, counters, device,
                 device_counters, peaks):
        self.cell = cell
        self.config = cell.config
        self.state = st
        self.counters = counters
        self.device_counters = device_counters
        self.theta = entry.theta(st)
        self.sizes_cells = [tuple(s) for s in st.sys.meta["sizes_cells"]]
        self.spans = spans
        self.device = device
        self.peaks = peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench.lib import registry, result
    cell = registry.find_cell(args.workload)
    try:
        require_chip(cell.chips)
    except NoChip as exc:
        log(f"bench/run.py: {exc}")
        return 3
    enable_cache()
    obj, checks = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    result.emit(obj, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
