"""Entry ``batch``: sealed-clip extraction through ``executor.run_clips``.

Traffic parameters (``bench/traffic/<mix>.json``):

  profile       the scene profile of ``video_synth``
  clip_frames   frames per clip
  clip_ids      the pool of distinct clips (``lib/frames``), ordered by
                the seed and rendered in set-up; the window reuses them
                cyclically
  trace_seconds the traced run's profiled window (a profile of the
                whole window outgrows the host's memory)

Set-up trains or loads the bank, renders the pool into the program's
render cache, runs the pool once through ``run_clips`` (every detector
bucket per window size, the proxy and the crop embedding the window
will use compile here, and only those), then times two clips to size
the window's clip list to the whole passes over the pool that fit in
``--seconds`` (at least one).  The probe sets the count, so a pass
time near ``--seconds`` over a whole number makes some runs one pass
shorter than others (on a v5e at 51 s: 4 or 5 passes of about 9.8 s).

The window is ONE ``run_clips`` call over that list.  ``frames_per_s``
is the video frames of all its clips, frames skipped by the gap
included, over the wall time of the call.  ``window(st, seconds)``
runs the first whole passes of the list that fit in ``seconds`` instead:
the traced run's profiled window.

The pool repeats within a window: the same clips run again in another
pass.  A later run of a clip is checked by equality with its first, so
a program that kept results by clip identity would pass the check and
read faster; no such cache may enter the program's path.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench.lib import frames as fr
from bench.lib import models
from bench.lib.observe import Records, observe
from bench.reference import compare


@dataclass
class State:
    cell: object
    sys: models.System
    pool: list
    per_pass: float = 0.0
    clip_list: list = field(default_factory=list)
    # every window since set-up: (its clips, what it observed, results)
    runs: List[tuple] = field(default_factory=list)
    window: Dict[str, float] = field(default_factory=dict)
    setup_notes: Dict[str, float] = field(default_factory=dict)


def _run(st: State, clips):
    from repro.core.executor import run_clips
    return run_clips(st.sys.bank, st.sys.params, clips)[0]


def setup(cell, seed: int, seconds: float, log,
          fresh: bool = False) -> State:
    tr = cell.traffic
    t0 = time.perf_counter()
    sys_ = models.build(cell.config, tr["profile"], log, cell.root)
    t1 = time.perf_counter()
    pool = fr.make_clips(tr["profile"], seed,
                         fr.pool_ids(tr, seed, fresh),
                         int(tr["clip_frames"]))
    W, H = sys_.params.det_res
    gap = sys_.params.gap
    n = fr.render(pool, [range(0, c.n_frames, gap) for c in pool], W, H)
    t2 = time.perf_counter()
    st = State(cell, sys_, pool)
    _run(st, pool)                       # compiles the cell's shapes
    t3 = time.perf_counter()
    probe = pool[:2]
    _run(st, probe)
    per_clip = (time.perf_counter() - t3) / len(probe)
    st.per_pass = per_clip * len(pool)
    n_clips = _passes(st, seconds) * len(pool)
    st.clip_list = [pool[i % len(pool)] for i in range(n_clips)]
    st.setup_notes = {"models_s": t1 - t0, "render_s": t2 - t1,
                      "rendered_frames": n, "warm_s": t3 - t2,
                      "probe_s_per_clip": per_clip, "clips": n_clips,
                      "trained": sys_.trained}
    return st


def _passes(st: State, seconds: float) -> int:
    """Whole passes over the pool that fit in ``seconds``: each clip of
    the pool runs equally often."""
    return max(1, int(seconds / max(st.per_pass, 1e-3)))


def window(st: State, seconds: Optional[float] = None) -> Dict[str, float]:
    clips = st.clip_list if seconds is None else \
        st.clip_list[:_passes(st, seconds) * len(st.pool)]
    records = Records()
    with observe(records):
        t0 = time.perf_counter()
        res = _run(st, clips)
        wall = time.perf_counter() - t0
    st.runs.append((clips, records, res))
    video = sum(c.n_frames for c in clips)
    st.window = {
        "wall_s": wall, "video_frames": video,
        "frames_processed": sum(r.frames_processed for r in res),
        "detector_windows": sum(r.detector_windows for r in res),
        "full_frames": sum(r.full_frames for r in res),
        "skipped_frames": sum(r.skipped_frames for r in res),
        "clips": len(res),
    }
    return {"frames_per_s": video / wall}


def _same(a, b) -> bool:
    if a.frame_ids != b.frame_ids or a.windows != b.windows:
        return False
    return all(np.array_equal(x, y) for x, y in zip(a.dets, b.dets))


def streams(st: State) -> List[compare.Stream]:
    """Every clip run of every window since set-up: the first run of
    each clip in full, a later run of the same clip by equality with it
    (in full where it differs)."""
    first: Dict[int, tuple] = {}
    out = []
    for clips, records, results in st.runs:
        recs = records.in_order()
        if len(recs) != len(results):
            raise RuntimeError(f"{len(recs)} observed runs for "
                               f"{len(results)} results")
        for clip, rec, res in zip(clips, recs, results):
            if rec.clip is not clip:
                raise RuntimeError("observed runs are out of clip order")
            seen = first.get(id(clip))
            if seen is not None and _same(seen[0], rec) and \
                    len(res.tracks) == len(seen[1].tracks) and all(
                        np.array_equal(a, b) for a, b in
                        zip(res.tracks, seen[1].tracks)):
                continue
            first.setdefault(id(clip), (rec, res))
            out.append(compare.Stream(rec.clip, rec.frame_ids, rec.windows,
                                      rec.dets, res.tracks))
    return out


def theta(st: State) -> dict:
    p = st.sys.params
    return {"det_res": list(p.det_res), "proxy_res": list(p.proxy_res),
            "det_conf": p.det_conf, "proxy_threshold": p.proxy_threshold,
            "gap": p.gap}


def check(st: State, control=None):
    W, H = st.sys.params.det_res
    return compare.check(streams(st), st.sys.weights, st.cell.config,
                         theta(st), lambda c, f: fr.frame(c, f, W, H),
                         control=control, root=st.cell.root)


def attempted(st: State):
    """(attempted, failed): clip runs of every window; a run that raised
    would have ended its window."""
    return sum(len(res) for _, _, res in st.runs), 0


def counters(st: State) -> dict:
    return dict(st.window)
