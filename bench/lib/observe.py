"""Read what the timed path produced, chunk by chunk.

``observe`` installs a pass-through around the executor's TRACK stage
(``executor.DEFAULT_STAGES``, the stage table every ``ClipExecutor``
copies at construction).  After the stage has run, it keeps references
to the chunk's frame ids, the proxy's window plan and the per-frame
detections, which the comparison with the reference reads once the
window has closed.  It changes no argument and no result, and costs one
list append a chunk.
"""
from __future__ import annotations

import itertools
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np


@dataclass
class RunRecord:
    """One executor run (a clip)."""
    run: int
    clip: object
    frame_ids: List[int] = field(default_factory=list)
    windows: List[list] = field(default_factory=list)     # per frame
    dets: List[np.ndarray] = field(default_factory=list)  # per frame


class Records:
    def __init__(self):
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self.runs: Dict[int, RunRecord] = {}

    def note(self, ctx, task) -> None:
        with self._lock:
            run = ctx.__dict__.get("_bench_run")
            if run is None:
                run = next(self._ids)
                ctx.__dict__["_bench_run"] = run
                self.runs[run] = RunRecord(run, ctx.clip)
            rec = self.runs[run]
        rec.frame_ids.extend(int(f) for f in task.frame_ids)
        rec.windows.extend(list(w) for w in task.plan.windows)
        rec.dets.extend(task.dets)

    def in_order(self) -> List[RunRecord]:
        return [self.runs[k] for k in sorted(self.runs)]


@contextmanager
def observe(records: Records):
    from repro.core import executor as ex
    orig = ex.DEFAULT_STAGES["track"]

    def track(ctx, task):
        out = orig(ctx, task)
        records.note(ctx, out)
        return out

    ex.DEFAULT_STAGES["track"] = track
    try:
        yield records
    finally:
        ex.DEFAULT_STAGES["track"] = orig
