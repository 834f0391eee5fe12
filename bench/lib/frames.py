"""Clips and frames for a run: the load generator, outside the system
under test.

A traffic mix names a fixed pool of clips, ``make_clip(profile, "test",
id)`` for its ``clip_ids``, and ``--seed`` orders them: every seed gives
the same work in another order, so runs of different seeds measure the
same thing.  (``clip_ids`` drawn from a seed give other content, which
``bench/readings.py --fresh`` uses to set the limits over many scenes.)
Their frames are rendered during set-up into the
program's own render cache (``pipeline.render_frame``), so the measured
window runs the system on decoded frames and the synthetic renderer
costs nothing inside it.  Rendering runs on a small thread pool (numpy
releases the interpreter lock for the pixel work).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence

import numpy as np

RENDER_WORKERS = 8


def clip_ids(seed: int, n: int) -> List[int]:
    """``n`` distinct clip ids from the seed (any whole number)."""
    rng = np.random.default_rng(int(seed) % (2 ** 63))
    ids: List[int] = []
    while len(ids) < n:
        i = int(rng.integers(0, 2 ** 31 - 1))
        if i not in ids:
            ids.append(i)
    return ids


def order(seed: int, n: int) -> List[int]:
    """A permutation of ``range(n)`` from the seed."""
    rng = np.random.default_rng(int(seed) % (2 ** 63))
    return [int(i) for i in rng.permutation(n)]


def make_clips(profile: str, seed: int, ids: Sequence[int], n_frames: int):
    """The pool's clips in the seed's order."""
    from repro.data.video_synth import make_clip
    return [make_clip(profile, "test", int(ids[i]), n_frames)
            for i in order(seed, len(ids))]


def pool_ids(traffic: dict, seed: int, fresh: bool = False) -> List[int]:
    """The mix's clip ids, or as many drawn from the seed."""
    ids = list(traffic["clip_ids"])
    return clip_ids(seed, len(ids)) if fresh else ids


def render(clips: Sequence, frame_ids: Sequence[Sequence[int]],
           W: int, H: int, workers: int = RENDER_WORKERS) -> int:
    """Render ``frame_ids[k]`` of ``clips[k]`` at (W, H) into the
    program's render cache.  Returns the number of frames."""
    from repro.core import pipeline as pl
    jobs = [(c, f) for c, ids in zip(clips, frame_ids) for f in ids]
    cap = getattr(pl, "_RENDER_CACHE_MAX", None)
    if cap is not None and len(jobs) > cap:
        raise ValueError(f"{len(jobs)} frames exceed the render cache's "
                         f"{cap} entries: the window would re-render")
    with ThreadPoolExecutor(workers) as ex:
        list(ex.map(lambda j: pl.render_frame(j[0], j[1], W, H), jobs))
    return len(jobs)


def frame(clip, f: int, W: int, H: int) -> np.ndarray:
    """One rendered frame (from the render cache when it is there)."""
    from repro.core import pipeline as pl
    return pl.render_frame(clip, f, W, H)[0]
