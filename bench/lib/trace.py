"""The device trace of a window and its reduction.

``capture`` runs the window under JAX's profiler (Python tracer off,
host tracer at its lowest level) inside a ``bench.window`` annotation,
whose host event gives the window's bounds on the profiler's clock and
its offset from ``time.perf_counter_ns``, the clock of the program's
spans.

``reduce`` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

  * busy: the union of the intervals in which an operation ran on each
    device (its ops line), clipped to the window, averaged over the
    chips used;
  * per-program device time: the device's modules line, summed by
    program name (``jit__detect_scores(...)`` -> ``jit__detect_scores``);
  * device_ops: the operations that took most time, summed by name;
  * idle_gaps: the longest gaps between busy intervals on the first
    chip, each named by the deepest program span open at its middle.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


@contextmanager
def capture(out_dir: str, box: dict):
    """Profile the body; ``box`` receives ``xplane`` (the file) and
    ``perf_t0`` (perf_counter_ns at the annotation's start)."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            box["perf_t0"] = time.perf_counter_ns()
            yield box
            box["perf_t1"] = time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    box["xplane"] = found[0] if found else None


def warm_profiler(out_dir: str) -> None:
    """Start and stop the profiler once, so that its one-time start-up
    (which slowed the first profiled window of a process about 3x on a
    v5e) falls in set-up, not in the window."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(out_dir)
    try:
        jnp.zeros(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    shutil.rmtree(out_dir, ignore_errors=True)


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def is_device(plane_name: str) -> bool:
    """An accelerator's plane (``/device:TPU:0``), not the host's nor a
    ``/device:CUSTOM:...`` one."""
    return bool(_DEVICE.match(plane_name))


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce(path: str, spans: Sequence = (), perf_t0: Optional[int] = None,
           chips: int = 1, ops_line: str = "XLA Ops",
           modules_line: str = "XLA Modules") -> dict:
    """-> {window_s, busy_s, per_chip_busy_s, programs {name: s},
    device_ops [[name, s]], idle_gaps [[name, s]]}."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    w0 = w1 = None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    w0, w1 = ev.start_ns, ev.start_ns + ev.duration_ns
    if w0 is None:
        raise ValueError(f"{path}: no {WINDOW} annotation")
    devs = sorted((p for p in pd.planes if is_device(p.name)),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    if not devs:
        raise ValueError(f"{path}: no device plane")
    busy, programs, ops = [], {}, {}
    first_union: List[Tuple[int, int]] = []
    for k, plane in enumerate(devs):
        iv = []
        for line in plane.lines:
            if line.name == ops_line:
                for ev in line.events:
                    s = max(ev.start_ns, w0)
                    e = min(ev.start_ns + ev.duration_ns, w1)
                    if e > s:
                        iv.append((s, e))
                        name = op_name(ev.name)
                        ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
            elif line.name == modules_line:
                for ev in line.events:
                    s = max(ev.start_ns, w0)
                    e = min(ev.start_ns + ev.duration_ns, w1)
                    if e > s:
                        name = program_name(ev.name)
                        programs[name] = programs.get(name, 0.0) \
                            + (e - s) / 1e9
        u = _union(iv)
        if k == 0:
            first_union = u
        busy.append(sum(e - s for s, e in u) / 1e9)
    gaps = []
    prev = w0
    for s, e in first_union + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[host_activity(spans, (a + b) // 2 - w0 + perf_t0)
              if perf_t0 is not None else "unknown", (b - a) / 1e9]
             for a, b in gaps[:10]]
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "per_chip_busy_s": busy, "programs": programs,
            "device_ops": [[n, s] for n, s in top], "idle_gaps": named}


def host_activity(spans: Sequence, t_perf: int) -> str:
    """The deepest program span open at ``t_perf`` (perf_counter ns)."""
    best, depth = "host (no span)", -1
    parents = {s.sid: s.parent for s in spans}
    for s in spans:
        if s.ts <= t_perf < s.ts + max(s.dur, 0):
            d, p = 0, s.parent
            while p is not None and d < 16:
                d, p = d + 1, parents.get(p)
            if d > depth:
                best, depth = s.name, d
    return best
