"""Streaming clip executor: the pluggable stage-graph scheduler for the
chunked MultiScope pipeline.

PR 1 restructured one clip into chunks of B frames with four stages per
chunk; this module extracts those stages behind an explicit stage graph
so HOW the stages are scheduled is pluggable and independent of WHAT
each stage computes:

  DECODE  — render B frames at detector resolution, charging the
            decode-cost ledger (``pipeline.render_frame``);
  PROXY   — one fused ``proxy_plan`` kernel dispatch for the chunk
            (score + threshold + detector-grid mapping on device), then
            host window planning from the kernel's grids + plan stats
            (``windows.plan_from_mapped``; ``fused_plan=False`` keeps
            the legacy score-map round-trip through ``plan_chunk``);
  DETECT  — cross-frame size-class batches through the detector, window
            crops via the ``window_gather_batch`` Pallas kernel, batch
            dims padded to power-of-two buckets; with a shared
            ``BatchBroker`` the dispatch itself coalesces windows
            across every concurrent run (see ``BatchBroker``);
  TRACK   — detections feed the tracker strictly in frame order (the
            only stage with cross-chunk state), candidate crop
            embeddings batched per chunk (``tracker.embed_dets_chunk``).

Two schedulers drive the graph:

  * ``SequentialScheduler`` — every stage of chunk k completes before
    chunk k+1 starts: exactly the PR-1 chunked engine.
  * ``StreamingScheduler`` — DECODE (and, with double buffering, the
    device upload) for chunk k+1 runs on a background thread while
    chunk k is in PROXY/DETECT/TRACK on the caller's thread.  The
    hand-off queue is bounded by ``prefetch_depth``, so at most that
    many decoded chunks (and device buffers) are in flight.

Buffer ownership: the decoded host chunk is owned by its ``ChunkTask``;
the padded device copy (``frames_dev``) is uploaded either eagerly by
the decode worker (double buffering: the upload of chunk k+1 overlaps
chunk k's detector work) or lazily by DETECT, is only ever needed for
sub-frame window gathers, and is donated back (deleted) as soon as
DETECT finishes so at most ``prefetch_depth`` device buffers exist.

Sharding attaches at the chunk boundary: chunks are independent through
DETECT, so stages 1-3 round-robin across ``ExecutorOptions.devices``
(default: all local devices), and a ``jax.sharding.Mesh`` can be passed
instead to shard each chunk's batch axis via the
``repro.distributed.sharding.LogicalRules`` helpers.  TRACK is always
sequenced in frame order on the caller's thread, which is what keeps
the executor's tracks BIT-IDENTICAL to ``pipeline.run_clip_frames``
(asserted by tests/test_executor.py) for every chunk size, prefetch
setting, and device assignment.

The chunk size B is tuner-visible: ``PipelineParams.chunk_size`` (None
means ``DEFAULT_CHUNK``) is proposed by the tuner's scheduler module
for sparse/skip-heavy θ and flows through here, ``windows.plan_chunk``
and ``tracker.embed_dets_chunk`` bucketing.

``RunResult.seconds`` semantics are unchanged: process CPU time plus
the charged decode ledger.  Decode CPU actually spent is measured with
``time.thread_time`` in whichever thread renders, so the ledger
arithmetic is exact even when decode overlaps compute.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.detector import next_bucket, nms
from repro.core.pipeline import (CELL_PX, ModelBank, PipelineParams,
                                 RunResult, det_grid, downsample_chunk,
                                 make_sizeset, map_proxy_grid,
                                 render_frame)
from repro.core.tracker import RecurrentTracker, embed_dets_chunk
from repro.core.windows import (ChunkPlan, full_frame_plan, plan_chunk,
                                plan_from_mapped)
from repro.data.video_synth import Clip
from repro.obs.metrics import REGISTRY, RunProfile, drift_enabled
from repro.obs.recorder import crash_dump
from repro.obs.trace import TRACER

DEFAULT_CHUNK = 16     # frames per chunk (B) when θ does not say

STAGES = ("decode", "proxy", "detect", "track")


def effective_chunk(params: PipelineParams,
                    override: Optional[int] = None) -> int:
    """The chunk size B for one run: explicit override > θ's
    ``chunk_size`` > ``DEFAULT_CHUNK``."""
    if override is not None:
        return int(override)
    b = getattr(params, "chunk_size", None)
    return int(b) if b else DEFAULT_CHUNK


@dataclass
class ExecutorOptions:
    """Scheduling knobs — orthogonal to θ (they never change tracks).

    ``prefetch``       — decode chunk k+1 on a background thread while
                         chunk k is in proxy/detect/track;
    ``prefetch_depth`` — max decoded chunks in flight (bounds host and
                         device memory);
    ``decode_workers`` — size of the decode worker pool per run
                         (default 1: the single implicit thread).  With
                         N > 1 workers, chunks decode concurrently and
                         a reorder gate hands them to the compute
                         thread strictly in chunk order, so TRACK stays
                         frame-ordered and tracks stay bit-identical;
                         in-flight decoded chunks are bounded by
                         ``prefetch_depth + decode_workers`` (queue
                         plus at most one chunk held per worker at the
                         gate);
    ``double_buffer``  — upload ``frames_dev`` in the decode worker so
                         the copy overlaps the previous chunk's
                         detector work (only when a proxy is active:
                         all-full-frame plans never need the buffer);
    ``devices``        — stage 1-3 dispatch targets, round-robinned per
                         chunk (default: ``jax.local_devices()``);
    ``mesh``           — optional ``jax.sharding.Mesh``; when set, each
                         chunk's batch axis is sharded through
                         ``LogicalRules`` instead of whole-chunk
                         round-robin;
    ``chunk_size``     — override θ's B (engine compat path);
    ``decode_pool``    — an externally owned ``DecodePool``: decode jobs
                         are submitted to its persistent shared workers
                         instead of spawning per-run threads (per-run
                         reorder gates keep TRACK frame-ordered);
    ``share_decode_pool`` — let ``run_clips`` create ONE pool shared by
                         the two in-flight clips (the pool is sized
                         ``max(2, decode_workers)`` so cross-clip decode
                         overlap survives the sharing);
    ``batch_broker``   — an externally owned ``BatchBroker``: DETECT
                         dispatches route through it so windows from
                         every run sharing the broker coalesce into one
                         consolidated detector batch per size class
                         (tracks stay bit-identical per stream —
                         detector rows are per-sample independent);
    ``fused_plan``     — PROXY uses the fused ``proxy_plan`` kernel
                         (score + threshold + detector-grid mapping on
                         device, ``windows.plan_from_mapped`` on the
                         stats) instead of pulling the full score map to
                         the host.  Plans, and therefore tracks, are
                         bit-identical either way;
    ``device_assign``  — TRACK runs each per-frame step as ONE fused
                         ``kernels.track_step`` dispatch (GRU + match
                         logits + cost + JV assignment on device)
                         instead of the host numpy twins.  Tracks are
                         bit-identical (the fastmath contract);
    ``device_tracker`` — TRACK holds its state in device slot buffers
                         and executes a whole chunk as one ``lax.scan``
                         dispatch (``tracker.DeviceTracker``; implies
                         the device step).  Tracks are bit-identical;
    ``track_broker``   — an externally owned ``TrackBroker``: device
                         track steps from every run sharing the broker
                         coalesce into one batched ``track_step``
                         dispatch (the per-frame live-fleet regime;
                         chunk-resident runs without a broker use the
                         scan instead).  Per-stream tracks stay
                         bit-identical — the fused step restricts its
                         JV solve to the canonical ``assoc_side``
                         square, so batch padding never perturbs it.
    """
    prefetch: bool = True
    prefetch_depth: int = 2
    decode_workers: int = 1
    double_buffer: bool = True
    devices: Optional[Sequence] = None
    mesh: Optional[object] = None
    chunk_size: Optional[int] = None
    decode_pool: Optional["DecodePool"] = None
    share_decode_pool: bool = True
    batch_broker: Optional["BatchBroker"] = None
    fused_plan: bool = True
    device_assign: bool = False
    device_tracker: bool = False
    track_broker: Optional["TrackBroker"] = None


@dataclass
class ChunkTask:
    """One chunk's state as it flows through the stage graph."""
    index: int
    frame_ids: List[int]
    frames: Optional[np.ndarray] = None        # (B, H, W, 3) host pixels
    charged: float = 0.0                       # decode ledger for chunk
    frames_dev: Optional[object] = None        # padded device buffer
    plan: Optional[ChunkPlan] = None
    dets: Optional[List[np.ndarray]] = None    # per-frame detections


class _WorkerFailure:
    def __init__(self, exc: BaseException):
        self.exc = exc


# ---------------------------------------------------------------------------
# Cross-stream batch broker (PROXY -> DETECT boundary)
# ---------------------------------------------------------------------------

class BrokerCancelled(RuntimeError):
    """The stream's broker registration was dropped while a request was
    pending: its windows are discarded, other streams are unaffected."""


class _BrokerHandle:
    """One stream's registration with a ``BatchBroker``.  Created lazily
    by ``_RunContext`` on the stream's first DETECT dispatch (so a run
    that never reaches DETECT never delays other streams' flushes) and
    closed when the run finishes or is cancelled."""

    __slots__ = ("broker", "active")

    def __init__(self, broker: "BatchBroker"):
        self.broker = broker
        self.active = True

    def detect(self, detector, frames, conf, origins, scales,
               n_valid: int) -> List[np.ndarray]:
        return self.broker._detect(self, detector, frames, conf,
                                   origins, scales, n_valid)

    def close(self) -> None:
        self.broker.unregister(self)


class _BrokerRequest:
    __slots__ = ("handle", "detector", "frames", "conf", "origins",
                 "scales", "n", "t_enq", "done", "result", "error")

    def __init__(self, handle, detector, frames, conf, origins, scales,
                 n: int):
        self.handle = handle
        self.detector = detector
        self.frames = frames            # (>= n, h, w, 3); rows >= n pad
        self.conf = conf
        self.origins = list(origins)
        self.scales = list(scales)
        self.n = n
        self.t_enq = 0.0                # monotonic at enqueue
        self.done = False
        self.result: Optional[List[np.ndarray]] = None
        self.error: Optional[BaseException] = None


class BatchBroker:
    """Coalesce DETECT dispatches across concurrent executor runs.

    Each run (a ``SegmentIngestor`` append, one clip of ``run_clips``, a
    camera thread) registers a handle; its DETECT stage submits one
    request per size class and blocks for the routed-back results, which
    keeps TRACK order per stream exactly as without the broker.  Pending
    requests from all streams flush together: same-shape requests (one
    pow2 size-class bucket, the existing padding scheme) concatenate
    into ONE consolidated ``detect_batch`` call whose per-window results
    split back per request.  Detector conv outputs are per-sample
    independent of batch composition and each window's detections are
    decoded from its own rows, so per-stream tracks are BIT-IDENTICAL to
    the broker-off path (asserted by tests/test_broker.py).

    Flush policy — whichever waiting stream first observes a trigger
    performs the flush inline (no dedicated thread), and the detector
    dispatch itself runs with the condition variable RELEASED: streams
    reaching DETECT while a batch computes enqueue into the next batch
    instead of convoying behind the lock.  Triggers:

      * every registered stream has a request pending (nobody else can
        join this batch), or
      * pending windows reach ``max_batch`` (the consolidated bucket is
        full), or
      * a request has waited ``linger_ms`` (bounded latency: a stream
        whose peers are decoding — or yielded zero windows this chunk —
        never stalls behind them).  The 10ms default is well under a
        frame period and long enough for streams decoding concurrently
        to coalesce their chunks' windows.

    A failing stream's handle is closed by its executor, dropping its
    pending requests with ``BrokerCancelled`` while everyone else's
    flush proceeds; ``close()`` drains whatever is still pending.

    Stats (read by benchmarks): ``dispatches`` consolidated detector
    calls, ``windows_in`` real windows served, ``batch_fill`` per-call
    valid/bucket occupancy.
    """

    def __init__(self, max_batch: int = 64, linger_ms: float = 10.0):
        self.max_batch = int(max_batch)
        self.linger = float(linger_ms) / 1e3
        self._cv = threading.Condition()
        self._pending: List[_BrokerRequest] = []    # guarded-by: _cv
        self._registered = 0                        # guarded-by: _cv
        self._waiting = 0                           # guarded-by: _cv
        self._closed = False                        # guarded-by: _cv
        self.dispatches = 0                         # guarded-by: _cv
        self.windows_in = 0                         # guarded-by: _cv
        self.batch_fill: List[float] = []           # guarded-by: _cv
        # registry mirrors (cached: registry reset zeroes in place)
        self._m_disp = REGISTRY.counter("broker.detect.dispatches")
        self._m_units = REGISTRY.counter("broker.detect.units_in")
        self._m_fill = REGISTRY.histogram("broker.detect.fill")
        self._m_wait = REGISTRY.histogram("broker.detect.linger_wait_ms")
        self._m_depth = REGISTRY.gauge("broker.detect.queue_depth")

    # -- stream side ----------------------------------------------------------

    def register(self) -> _BrokerHandle:
        with self._cv:
            if self._closed:
                raise RuntimeError("BatchBroker is closed")
            self._registered += 1
            return _BrokerHandle(self)

    def unregister(self, handle: _BrokerHandle) -> None:
        with self._cv:
            if not handle.active:
                return
            handle.active = False
            self._registered -= 1
            for req in self._pending:
                if req.handle is handle:
                    req.error = BrokerCancelled(
                        "stream dropped with a request in flight")
                    req.done = True
            self._pending = [r for r in self._pending if not r.done]
            self._cv.notify_all()

    def close(self) -> None:
        """Drain-on-close: flush whatever is pending, then refuse new
        work.  Idempotent."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            batch, self._pending = self._pending, []
            if batch:
                stats = self._flush(batch)
                self._apply_stats(stats)
            self._cv.notify_all()

    def _detect(self, handle: _BrokerHandle, detector, frames, conf,
                origins, scales, n_valid: int) -> List[np.ndarray]:
        """Submit one size-class request and block for its results.
        ``frames``: (>= n_valid, h, w, 3) host or device rows; rows past
        ``n_valid`` are padding and are dropped before consolidation."""
        if n_valid == 0:
            return []
        req = _BrokerRequest(handle, detector, frames, conf, origins,
                             scales, n_valid)
        cv = self._cv
        cv.acquire()
        try:
            if self._closed:
                raise RuntimeError("BatchBroker is closed")
            if not handle.active:
                raise BrokerCancelled("handle already closed")
            # no notify on enqueue: this thread checks the flush trigger
            # itself before waiting, and every other waiter re-checks at
            # its own linger deadline — waking 15 peers per enqueue on a
            # single core is pure context-switch churn
            req.t_enq = time.monotonic()
            self._pending.append(req)
            self._waiting += 1
            self._m_depth.set(len(self._pending))
            try:
                deadline = req.t_enq + self.linger
                while not req.done:
                    if self._pending and (
                            self._should_flush()
                            or time.monotonic() >= deadline):
                        batch, self._pending = self._pending, []
                        # dispatch WITHOUT the lock: streams reaching
                        # DETECT while this batch computes enqueue into
                        # the next one instead of convoying behind it
                        cv.release()
                        try:
                            stats = self._flush(batch)
                        finally:
                            cv.acquire()
                        self._apply_stats(stats)
                        cv.notify_all()
                    elif self._pending:
                        cv.wait(timeout=max(
                            deadline - time.monotonic(), 1e-4))
                    else:
                        # our request rode out with another thread's
                        # in-flight flush; its completion (or a cancel)
                        # notifies under the lock
                        cv.wait()
            finally:
                self._waiting -= 1
        finally:
            cv.release()
        if req.error is not None:
            raise req.error
        return req.result

    # -- flush side -----------------------------------------------------------

    # holds-lock: _cv
    def _should_flush(self) -> bool:
        if not self._pending:
            return False
        if self._waiting >= self._registered:
            return True
        return sum(r.n for r in self._pending) >= self.max_batch

    # holds-lock: _cv
    def _apply_stats(self, stats: List[Tuple[int, int]]) -> None:
        """Fold per-dispatch (valid, bucket) counts into the public
        counters; called with the condition variable held (dispatches
        themselves can overlap across flushing threads)."""
        for total, bucket in stats:
            self.dispatches += 1
            self.windows_in += total
            self.batch_fill.append(total / bucket)
            self._m_disp.inc()
            self._m_units.inc(total)
            self._m_fill.observe(total / bucket)

    def _flush(self, batch: List[_BrokerRequest]
               ) -> List[Tuple[int, int]]:
        # how long the oldest rider lingered before this flush fired
        wait_ms = max(0.0, (time.monotonic()
                            - min(r.t_enq for r in batch)) * 1e3)
        self._m_wait.observe(wait_ms)
        fsp = None
        if TRACER.enabled:
            fsp = TRACER.open(
                "broker.detect.flush", "broker",
                args={"requests": len(batch),
                      "streams": len({id(r.handle) for r in batch}),
                      "windows": sum(r.n for r in batch),
                      "wait_ms": round(wait_ms, 3)})
        groups: Dict[tuple, List[_BrokerRequest]] = {}
        for req in batch:
            key = (id(req.detector), float(req.conf),
                   tuple(req.frames.shape[1:3]))
            groups.setdefault(key, []).append(req)
        stats: List[Tuple[int, int]] = []
        for reqs in groups.values():
            d0 = time.perf_counter_ns() if fsp is not None else 0
            try:
                stats.append(self._dispatch(reqs))
            except BaseException as exc:
                for r in reqs:
                    r.error = exc
                    r.done = True
            else:
                if fsp is not None:
                    total, bucket = stats[-1]
                    TRACER.emit(
                        "broker.detect.dispatch", "broker", ts=d0,
                        dur=time.perf_counter_ns() - d0, parent=fsp.sid,
                        args={"windows": total, "bucket": bucket,
                              "streams": len(reqs),
                              "fill": round(total / bucket, 3)})
        if fsp is not None:
            TRACER.close(fsp)
        return stats

    def _dispatch(self, reqs: List[_BrokerRequest]) -> Tuple[int, int]:
        detector = reqs[0].detector
        total = sum(r.n for r in reqs)
        bucket = next_bucket(total)
        if len(reqs) == 1 and reqs[0].frames.shape[0] == bucket:
            # lone already-bucketed request (a stream flushing alone at
            # its linger deadline): feed it through untouched — for
            # device-side crops this skips the host round-trip entirely,
            # making a solo-stream broker run cost the same as no broker
            r = reqs[0]
            dets = detector.detect_batch(r.frames, r.conf,
                                         origins=r.origins,
                                         scales=r.scales, n_valid=r.n)
            r.result = dets
            r.done = True
            return total, bucket
        parts = [r.frames[:r.n] for r in reqs]
        # consolidate in HOST memory even when parts are device arrays:
        # a jnp.concatenate here would specialize one XLA program per
        # distinct combination of part counts/shapes (unbounded across a
        # fleet), while the numpy stack keeps the jit universe to the
        # same pow2 detect buckets the solo path already compiles
        stack = np.zeros((bucket,) + tuple(parts[0].shape[1:]),
                         np.float32)
        ofs = 0
        for p in parts:
            stack[ofs:ofs + len(p)] = np.asarray(p)
            ofs += len(p)
        origins = [o for r in reqs for o in r.origins]
        scales = [s for r in reqs for s in r.scales]
        dets = detector.detect_batch(stack, reqs[0].conf,
                                     origins=origins, scales=scales,
                                     n_valid=total)
        ofs = 0
        for r in reqs:
            r.result = dets[ofs:ofs + r.n]
            ofs += r.n
            r.done = True
        return total, bucket


# ---------------------------------------------------------------------------
# Cross-stream track-step broker (TRACK stage, per-frame device regime)
# ---------------------------------------------------------------------------

class _TrackHandle:
    """One stream's registration with a ``TrackBroker``.  Attached to the
    stream's tracker as ``_track_handle`` by ``_RunContext`` and closed
    when the run finishes or is cancelled."""

    __slots__ = ("broker", "active")

    def __init__(self, broker: "TrackBroker"):
        self.broker = broker
        self.active = True

    def step(self, h_r, tbox_r, alive_r, te_gap_r, te_match, x, dbox,
             dvalid, thr, params, table, *, params_key):
        return self.broker._step(self, (h_r, tbox_r, alive_r, te_gap_r,
                                        te_match, x, dbox, dvalid),
                                 thr, params, table, params_key)

    def close(self) -> None:
        self.broker.unregister(self)


class _TrackRequest:
    __slots__ = ("handle", "arrs", "thr", "params", "table", "key",
                 "t_enq", "done", "result", "error")

    def __init__(self, handle, arrs, thr, params, table, key):
        self.handle = handle
        self.arrs = arrs                # the 8 (Q, ...) stream arrays
        self.thr = thr
        self.params = params
        self.table = table
        self.key = key                  # flush-group key
        self.t_enq = 0.0                # monotonic at enqueue
        self.done = False
        self.result = None
        self.error: Optional[BaseException] = None


class TrackBroker:
    """Coalesce per-frame device track steps across concurrent runs.

    The fused ``kernels.track_step`` batches over a leading K axis of
    independent streams; in the live per-frame regime (a fleet of
    ``SegmentIngestor`` cameras appending a frame or two at a time),
    each stream alone would dispatch K=1 steps.  A shared broker lets
    those steps ride one dispatch: each stream's ``assign="device"``
    tracker submits its step operands and blocks for the routed-back
    slice, so TRACK order per stream is exactly as without the broker.

    Same flush discipline as ``BatchBroker`` (whichever waiting stream
    first observes a trigger flushes inline with the lock released):
    every registered stream pending, ``max_streams`` pending, or a
    request older than ``linger_ms``.  Streams group by (tracker
    params, threshold, head dims); a group's slot buffers pad to the
    widest stream's Q and the batch axis pads to a pow2 bucket, both of
    which are bit-invariant for the real rows — the kernel restricts
    its JV solve to the canonical ``assoc_side`` square derived from
    the LIVE/VALID counts, so padding rows never perturb it (asserted
    by tests/test_device_tracker.py).

    Stats (read by benchmarks): ``dispatches`` consolidated kernel
    calls, ``steps_in`` real stream-steps served, ``stream_fill``
    per-call stream counts."""

    def __init__(self, max_streams: int = 16, linger_ms: float = 5.0):
        self.max_streams = int(max_streams)
        self.linger = float(linger_ms) / 1e3
        self._cv = threading.Condition()
        self._pending: List[_TrackRequest] = []     # guarded-by: _cv
        self._registered = 0                        # guarded-by: _cv
        self._waiting = 0                           # guarded-by: _cv
        self._closed = False                        # guarded-by: _cv
        self.dispatches = 0                         # guarded-by: _cv
        self.steps_in = 0                           # guarded-by: _cv
        self.stream_fill: List[int] = []            # guarded-by: _cv
        # registry mirrors (cached: registry reset zeroes in place)
        self._m_disp = REGISTRY.counter("broker.track.dispatches")
        self._m_units = REGISTRY.counter("broker.track.units_in")
        self._m_fill = REGISTRY.histogram("broker.track.fill")
        self._m_wait = REGISTRY.histogram("broker.track.linger_wait_ms")
        self._m_depth = REGISTRY.gauge("broker.track.queue_depth")

    # -- stream side ----------------------------------------------------------

    def register(self) -> _TrackHandle:
        with self._cv:
            if self._closed:
                raise RuntimeError("TrackBroker is closed")
            self._registered += 1
            return _TrackHandle(self)

    def unregister(self, handle: _TrackHandle) -> None:
        with self._cv:
            if not handle.active:
                return
            handle.active = False
            self._registered -= 1
            for req in self._pending:
                if req.handle is handle:
                    req.error = BrokerCancelled(
                        "stream dropped with a track step in flight")
                    req.done = True
            self._pending = [r for r in self._pending if not r.done]
            self._cv.notify_all()

    def close(self) -> None:
        """Drain-on-close: flush whatever is pending, then refuse new
        work.  Idempotent."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            batch, self._pending = self._pending, []
            if batch:
                stats = self._flush(batch)
                self._apply_stats(stats)
            self._cv.notify_all()

    def _step(self, handle: _TrackHandle, arrs, thr, params, table,
              params_key):
        Q, H = arrs[0].shape
        e = arrs[5].shape[1]
        key = (params_key, float(np.asarray(thr).reshape(-1)[0]), H, e)
        req = _TrackRequest(handle, arrs, thr, params, table, key)
        cv = self._cv
        cv.acquire()
        try:
            if self._closed:
                raise RuntimeError("TrackBroker is closed")
            if not handle.active:
                raise BrokerCancelled("handle already closed")
            req.t_enq = time.monotonic()
            self._pending.append(req)
            self._waiting += 1
            self._m_depth.set(len(self._pending))
            try:
                deadline = req.t_enq + self.linger
                while not req.done:
                    if self._pending and (
                            self._should_flush()
                            or time.monotonic() >= deadline):
                        batch, self._pending = self._pending, []
                        cv.release()
                        try:
                            stats = self._flush(batch)
                        finally:
                            cv.acquire()
                        self._apply_stats(stats)
                        cv.notify_all()
                    elif self._pending:
                        cv.wait(timeout=max(
                            deadline - time.monotonic(), 1e-4))
                    else:
                        cv.wait()
            finally:
                self._waiting -= 1
        finally:
            cv.release()
        if req.error is not None:
            raise req.error
        return req.result

    # -- flush side -----------------------------------------------------------

    # holds-lock: _cv
    def _should_flush(self) -> bool:
        if not self._pending:
            return False
        if self._waiting >= self._registered:
            return True
        return len(self._pending) >= self.max_streams

    # holds-lock: _cv
    def _apply_stats(self, stats: List[int]) -> None:
        for k in stats:
            self.dispatches += 1
            self.steps_in += k
            self.stream_fill.append(k)
            self._m_disp.inc()
            self._m_units.inc(k)
            self._m_fill.observe(float(k))

    def _flush(self, batch: List[_TrackRequest]) -> List[int]:
        wait_ms = max(0.0, (time.monotonic()
                            - min(r.t_enq for r in batch)) * 1e3)
        self._m_wait.observe(wait_ms)
        fsp = None
        if TRACER.enabled:
            fsp = TRACER.open(
                "broker.track.flush", "broker",
                args={"requests": len(batch),
                      "streams": len({id(r.handle) for r in batch}),
                      "wait_ms": round(wait_ms, 3)})
        groups: Dict[tuple, List[_TrackRequest]] = {}
        for req in batch:
            groups.setdefault(req.key, []).append(req)
        stats: List[int] = []
        for reqs in groups.values():
            d0 = time.perf_counter_ns() if fsp is not None else 0
            try:
                stats.append(self._dispatch(reqs))
            except BaseException as exc:
                for r in reqs:
                    r.error = exc
                    r.done = True
            else:
                if fsp is not None:
                    TRACER.emit(
                        "broker.track.dispatch", "broker", ts=d0,
                        dur=time.perf_counter_ns() - d0, parent=fsp.sid,
                        args={"streams": len(reqs)})
        if fsp is not None:
            TRACER.close(fsp)
        return stats

    def _dispatch(self, reqs: List[_TrackRequest]) -> int:
        from repro.kernels.track_step import track_step
        K = len(reqs)
        Kb = next_bucket(K)             # bound the jit universe to pow2
        Qm = max(r.arrs[0].shape[0] for r in reqs)
        # pad every stream to the widest slot bucket and stack: padding
        # rows are dead (alive = dvalid = 0), so the assoc_side-
        # restricted solve never sees them and real rows come back
        # bit-identical to a solo dispatch
        stacked = []
        for i, a in enumerate(zip(*(r.arrs for r in reqs))):
            tail = a[0].shape[1:]
            buf = np.zeros((Kb, Qm) + tail, np.float32)
            for k, part in enumerate(a):
                buf[k, :part.shape[0]] = part
            stacked.append(buf)
        r0 = reqs[0]
        out = track_step(*stacked, r0.thr, r0.params, r0.table)
        matched, h_upd, h_new = (np.asarray(o) for o in out)
        for k, r in enumerate(reqs):
            q = r.arrs[0].shape[0]
            r.result = (matched[k, :q], h_upd[k, :q], h_new[k, :q])
            r.done = True
        return K


def _auto_axes(mesh):
    """The same devices and axis names with every axis Auto.  A chunk
    is placed on the mesh and XLA partitions the stages from there;
    Explicit axes (``jax.make_mesh``'s default) would instead demand an
    output sharding from every gather in the DETECT stage."""
    from jax.sharding import AxisType, Mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


class _RunContext:
    """Per-clip derived state shared by every stage.

    ``frame_ids`` (default: θ's full gap progression over the clip)
    restricts the run to an explicit frame list — the live-ingestion
    path (``repro.stream``) runs one appended SEGMENT of an open clip
    at a time.  ``tracker`` injects an existing tracker instead of a
    fresh one, so TRACK state (active tracks, GRU hidden state, id
    counter) carries across segment runs; the stage graph itself never
    knows whether it is running a whole clip or a resumed slice."""

    def __init__(self, bank: ModelBank, params: PipelineParams,
                 clip: Clip, options: ExecutorOptions,
                 device_offset: int = 0,
                 frame_ids: Optional[Sequence[int]] = None,
                 tracker: Optional[object] = None):
        self.bank = bank
        self.params = params
        self.clip = clip
        self.cfg = bank.cfg
        self.chunk = effective_chunk(params, options.chunk_size)
        self.W, self.H = params.det_res
        self.proxy = bank.proxies.get(params.proxy_res) \
            if params.proxy_res is not None else None
        self.sizeset = make_sizeset(bank, params)
        self.grid = det_grid(params.det_res)
        self.detector = bank.detectors[params.det_arch]
        if tracker is not None:
            self.tracker: object = tracker
        else:
            from repro.core.pipeline import make_tracker
            self.tracker = make_tracker(
                bank, params, device_assign=options.device_assign,
                device_tracker=options.device_tracker)
        self.batch_embed = isinstance(self.tracker, RecurrentTracker)
        # cross-stream track-step broker: attach a handle to any
        # device-assign recurrent tracker (injected trackers included —
        # the live fleet passes resumed trackers through ``start``)
        self._track_broker = options.track_broker
        self.track_handle: Optional[_TrackHandle] = None
        if self._track_broker is not None and self.batch_embed \
                and getattr(self.tracker, "assign", "host") == "device":
            self.track_handle = self._track_broker.register()
            self.tracker._track_handle = self.track_handle
        self.devices = list(options.devices) if options.devices \
            else jax.local_devices()
        self.device_offset = device_offset
        self.sharding = None
        if options.mesh is not None:
            from repro.distributed.sharding import LogicalRules
            rules = LogicalRules(_auto_axes(options.mesh))
            self.sharding = rules.named_sharding(
                (self.chunk, self.H, self.W, 3),
                ("batch", None, None, None))
        # upload in the decode worker only when the buffer can actually
        # be used: sub-frame gathers require an active proxy, and the
        # previous chunk's plan is the cheap predictor of whether this
        # one will gather at all (skip-heavy θ would otherwise pay a
        # per-chunk host-to-device copy that DETECT deletes unused)
        self.predecode_upload = bool(options.double_buffer
                                     and self.proxy is not None)
        self.prev_chunk_gathered = False    # benign cross-thread read
        self.fused_plan = bool(options.fused_plan
                               and self.proxy is not None)
        self._broker = options.batch_broker
        self.broker_handle: Optional[_BrokerHandle] = None
        self.frame_ids = list(frame_ids) if frame_ids is not None \
            else list(range(0, clip.n_frames, params.gap))
        # ledger + RunResult counters, accumulated by TRACK (the only
        # stage that is strictly sequenced)
        self.charged = 0.0
        self.n_windows = 0
        self.full_frames = 0
        self.skipped = 0
        # per-stage wall/CPU + dispatch profile (obs.metrics.RunProfile:
        # the one assembly point for RunResult.stage_seconds); decode may
        # run on several workers, the profile carries the lock
        self.profile = RunProfile(STAGES)
        self._disp_track0 = int(getattr(self.tracker, "dispatches", 0))
        # observability: stream label for spans/gauges, plus the run's
        # root span (children emitted from worker threads parent to it
        # by explicit id)
        self.stream = f"{clip.profile.name}/{clip.split}{clip.clip_id}"
        self.run_span = None
        if TRACER.enabled:
            self.run_span = TRACER.open(
                "run", "executor", stream=self.stream,
                args={"frames": len(self.frame_ids),
                      "chunk": self.chunk})
        # per-frame proxy positive-cell fractions (drift monitoring
        # only; PROXY runs on the draining thread in chunk order, so
        # appends stay frame-ordered without a lock)
        self.proxy_fracs: Optional[List[float]] = \
            [] if drift_enabled() else None

    def broker(self) -> Optional[_BrokerHandle]:
        """The run's broker handle, registered lazily on the first
        DETECT dispatch (only streams that actually detect take part in
        the broker's all-streams-pending flush trigger).  DETECT runs on
        the draining thread only, so no lock is needed."""
        if self._broker is not None and self.broker_handle is None:
            self.broker_handle = self._broker.register()
        return self.broker_handle

    def close(self) -> None:
        """Release cross-run resources (the broker registrations);
        called by the executor when the run finishes or is cancelled."""
        if self.broker_handle is not None:
            self.broker_handle.close()
            self.broker_handle = None
        self._broker = None
        if self.track_handle is not None:
            if getattr(self.tracker, "_track_handle", None) \
                    is self.track_handle:
                self.tracker._track_handle = None
            self.track_handle.close()
            self.track_handle = None
        self._track_broker = None
        if self.run_span is not None and self.run_span.dur < 0:
            TRACER.close(self.run_span,
                         args={"windows": self.n_windows,
                               "skipped": self.skipped})

    def device_for(self, task: ChunkTask):
        return self.devices[(self.device_offset + task.index)
                            % len(self.devices)]

    def placed(self, task: ChunkTask):
        """Make the chunk's round-robin device the default for its
        PROXY and DETECT dispatches, so host inputs (proxy frames,
        full-frame detector batches) land where its uploaded buffer
        does.  A mesh places by sharding instead."""
        if self.sharding is not None:
            return contextlib.nullcontext()
        return jax.default_device(self.device_for(task))

    def upload(self, task: ChunkTask):
        """Pad the chunk to B frames (one gather jit shape) and place it
        on this chunk's device / mesh sharding."""
        padded = np.zeros((self.chunk, self.H, self.W, 3), np.float32)
        padded[:task.frames.shape[0]] = task.frames
        if self.sharding is not None:
            return jax.device_put(padded, self.sharding)
        if len(self.devices) > 1:
            return jax.device_put(padded, self.device_for(task))
        return jnp.asarray(padded)


# ---------------------------------------------------------------------------
# The four stages
# ---------------------------------------------------------------------------

def stage_decode(ctx: _RunContext, task: ChunkTask) -> ChunkTask:
    """Render the chunk at detector resolution, charging the ledger.

    ``time.thread_time`` measures the CPU actually spent rendering in
    THIS thread, so the charge (ledger cost minus actual cost) stays
    exact whether decode runs inline or on the prefetch worker."""
    B = len(task.frame_ids)
    frames = np.empty((B, ctx.H, ctx.W, 3), np.float32)
    charged = 0.0
    for k, f in enumerate(task.frame_ids):
        t_r = time.thread_time()
        frame, cost = render_frame(ctx.clip, f, ctx.W, ctx.H)
        charged += cost - (time.thread_time() - t_r)
        frames[k] = frame
    task.frames = frames
    task.charged = charged
    if ctx.predecode_upload and ctx.prev_chunk_gathered:
        task.frames_dev = ctx.upload(task)
    return task


def stage_proxy(ctx: _RunContext, task: ChunkTask) -> ChunkTask:
    """Proxy-score the whole chunk in one dispatch and plan windows.

    The default path is the fused ``proxy_plan`` kernel: threshold and
    detector-grid mapping happen on device and only the mapped int8
    grids + per-frame plan stats cross to the host, where
    ``plan_from_mapped`` takes exact shortcuts on the stats.  The
    legacy path (``fused_plan=False``) pulls the score map back and
    maps/plans fully on the host; both produce bit-identical plans."""
    with ctx.placed(task):
        if ctx.proxy is not None:
            ctx.profile.dispatch("proxy")
            pframes = downsample_chunk(task.frames, ctx.proxy.resolution)
            if ctx.fused_plan:
                grids, stats = ctx.proxy.plan_batch(
                    pframes, ctx.params.proxy_threshold, ctx.grid)
                task.plan = plan_from_mapped(grids, stats, ctx.sizeset,
                                             ctx.cfg.windows.max_windows,
                                             chunk_size=ctx.chunk)
            else:
                _, pos = ctx.proxy.scores_batch(pframes,
                                                ctx.params.proxy_threshold)
                grids = [map_proxy_grid(p, ctx.grid) for p in pos]
                task.plan = plan_chunk(grids, ctx.sizeset,
                                       ctx.cfg.windows.max_windows,
                                       chunk_size=ctx.chunk)
            if ctx.proxy_fracs is not None:
                # drift signal: positive-cell fraction per REAL frame (an
                # observer of grids the plan already computed — rows past
                # the chunk's frame count are padding)
                g = np.asarray(grids)[:len(task.frame_ids)]
                fracs = (g > 0).mean(axis=tuple(range(1, g.ndim)))
                ctx.proxy_fracs.extend(float(v) for v in fracs)
        else:
            task.plan = full_frame_plan(len(task.frame_ids), ctx.sizeset)
        return task


def stage_detect(ctx: _RunContext, task: ChunkTask) -> ChunkTask:
    """Cross-frame bucketed detection; reassemble per-frame detections
    in the exact order the per-frame path would have produced them."""
    with ctx.placed(task):
        detector = ctx.detector
        W, H = ctx.W, ctx.H
        plan, frames = task.plan, task.frames
        frames_dev = task.frames_dev
        per_window: Dict[Tuple[int, int], np.ndarray] = {}
        for size, entries in plan.by_size.items():
            pw, ph = size[0] * CELL_PX, size[1] * CELL_PX
            n = len(entries)
            origins = [(x * CELL_PX / W, y * CELL_PX / H)
                       for (_, x, y, _) in entries]
            scales = [(pw / W, ph / H)] * n
            broker = ctx.broker()
            ctx.profile.dispatch("detect")
            if (pw, ph) == (W, H):
                # full-frame windows: the crop is the frame itself
                stack = frames[[slot for (slot, _, _, _) in entries]]
                if broker is not None:
                    dets = broker.detect(detector, stack,
                                         ctx.params.det_conf,
                                         origins, scales, n)
                else:
                    dets = detector.detect_batch_bucketed(
                        stack, ctx.params.det_conf, origins=origins,
                        scales=scales)
            else:
                if frames_dev is None:       # lazy path (no double buffer)
                    frames_dev = ctx.upload(task)
                tbl = np.zeros((next_bucket(n), 3), np.int32)
                for k, (slot, x, y, _) in enumerate(entries):
                    tbl[k] = (slot, y, x)
                from repro.kernels.window_gather import window_gather_batch
                crops = window_gather_batch(frames_dev, tbl,
                                            win_h=ph, win_w=pw, cell=CELL_PX)
                # crops stay device-side: detect_batch feeds them straight
                # into the detector without a host round-trip
                if broker is not None:
                    dets = broker.detect(detector, crops,
                                         ctx.params.det_conf,
                                         origins, scales, n)
                else:
                    dets = detector.detect_batch(
                        crops, ctx.params.det_conf, origins=origins,
                        scales=scales, n_valid=n)
            for (slot, _, _, wi), d in zip(entries, dets):
                per_window[(slot, wi)] = d

        merged: List[np.ndarray] = []
        for slot, wins in enumerate(plan.windows):
            if not wins:
                merged.append(np.zeros((0, 5), np.float32))
            elif len(wins) == 1 and wins[0][2] == ctx.sizeset.full:
                # the per-frame fast path applies no cross-window NMS
                merged.append(per_window[(slot, 0)])
            else:
                by_size_frame: Dict[Tuple[int, int], List[int]] = {}
                for wi, (_, _, s) in enumerate(wins):
                    by_size_frame.setdefault(s, []).append(wi)
                parts = [per_window[(slot, wi)]
                         for wis in by_size_frame.values() for wi in wis]
                merged.append(nms(np.concatenate(parts)))
        task.dets = merged
        # steer the decode worker's eager upload (a stale read just means
        # one lazy upload): this chunk gathered iff any size class was
        # sub-frame
        ctx.prev_chunk_gathered = any(
            (s[0] * CELL_PX, s[1] * CELL_PX) != (W, H)
            for s in plan.by_size)
        # donate the device buffer back: DETECT is its last consumer, and
        # freeing it here bounds in-flight device memory to prefetch_depth
        if frames_dev is not None:
            task.frames_dev = None
            frames_dev.delete()
        return task


def stage_track(ctx: _RunContext, task: ChunkTask) -> ChunkTask:
    """Feed the tracker strictly in frame order; accumulate counters and
    the decode ledger.  The crop CNN runs once per chunk, and the whole
    chunk goes through ``step_chunk`` — a per-frame loop on the base
    tracker, ONE ``lax.scan`` dispatch on ``DeviceTracker``."""
    for wins in task.plan.windows:
        ctx.n_windows += len(wins)
        if len(wins) == 1 and wins[0][2] == ctx.sizeset.full:
            ctx.full_frames += 1
        if not wins:
            ctx.skipped += 1
    ctx.charged += task.charged
    if ctx.batch_embed:
        ctx.profile.dispatch("embed")
        embeds = embed_dets_chunk(ctx.bank.tracker_params,
                                  ctx.cfg.tracker, task.frames,
                                  task.dets,
                                  min_bucket=max(8, ctx.chunk // 2))
        ctx.tracker.step_chunk(task.frame_ids, task.dets, task.frames,
                               embeds=embeds)
    else:
        for k, f in enumerate(task.frame_ids):
            ctx.tracker.step(f, task.dets[k], task.frames[k])
    task.frames = None
    return task


DEFAULT_STAGES: Dict[str, Callable[[_RunContext, ChunkTask], ChunkTask]] \
    = {"decode": stage_decode, "proxy": stage_proxy,
       "detect": stage_detect, "track": stage_track}


def _timed(name: str, fn: Callable) -> Callable:
    """Wrap a stage so each call accumulates wall + thread-CPU seconds
    into the run's per-stage profile.  ``thread_time`` counts only the
    calling thread, so overlapped stages (decode on workers, compute on
    the draining thread) sum to honest per-stage CPU rather than
    double-counting each other.  With tracing on, the same interval is
    also emitted as a ``stage.{name}`` span parented to the run's root
    (explicitly — decode runs on worker threads whose thread-local span
    stack is empty)."""
    span_name = f"stage.{name}"

    def wrapper(ctx: _RunContext, task: ChunkTask) -> ChunkTask:
        t0 = time.perf_counter_ns()
        c0 = time.thread_time_ns()
        try:
            return fn(ctx, task)
        finally:
            dur = time.perf_counter_ns() - t0
            proc = time.thread_time_ns() - c0
            ctx.profile.note_stage(name, dur / 1e9, proc / 1e9)
            if TRACER.enabled:
                root = ctx.run_span
                TRACER.emit(span_name, "stage", ts=t0, dur=dur,
                            proc=proc, stream=ctx.stream,
                            chunk=task.index,
                            parent=root.sid if root is not None
                            else None)
    return wrapper


# ---------------------------------------------------------------------------
# Schedulers
# ---------------------------------------------------------------------------

class SequentialScheduler:
    """Reference scheduling: every stage of chunk k completes before
    chunk k+1 starts — the PR-1 chunked engine, stage graph edition."""

    def start(self, ctx: _RunContext, tasks: List[ChunkTask],
              stages: Dict[str, Callable]):
        return iter(tasks)

    def cancel(self, ctx: _RunContext, handle) -> None:
        pass                          # nothing runs ahead

    def drain(self, ctx: _RunContext, handle,
              stages: Dict[str, Callable]) -> None:
        for task in handle:
            for name in STAGES:
                task = stages[name](ctx, task)


class StreamingScheduler:
    """DECODE runs ahead on a pool of ``workers`` background threads
    with a bounded hand-off queue; PROXY/DETECT/TRACK run on the
    draining thread in chunk order.

    With one worker the queue itself preserves chunk order.  With a
    pool, workers claim chunk indices from a shared iterator and a
    reorder gate admits each decoded chunk to the queue only when every
    earlier chunk has been enqueued — so the draining thread (and with
    it TRACK) still sees chunks strictly in frame order, and tracks
    stay bit-identical to the single-thread schedule for any pool size
    (tests/test_executor.py).  A worker holds at most one decoded chunk
    while waiting at the gate, so in-flight host memory is bounded by
    ``depth + workers`` chunks."""

    def __init__(self, depth: int = 2, workers: int = 1):
        self.depth = max(1, int(depth))
        self.workers = max(1, int(workers))

    def start(self, ctx: _RunContext, tasks: List[ChunkTask],
              stages: Dict[str, Callable]):
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        it = iter(enumerate(tasks))
        it_lock = threading.Lock()
        gate = threading.Condition()
        state = {"next": 0, "failed": False}

        def worker():
            while not stop.is_set():
                with it_lock:
                    nxt = next(it, None)
                if nxt is None:
                    return
                i, task = nxt
                try:
                    decoded = stages["decode"](ctx, task)
                except BaseException as exc:    # surfaced by drain()
                    with gate:
                        state["failed"] = True
                        gate.notify_all()
                    q.put(_WorkerFailure(exc))
                    return
                with gate:
                    while state["next"] != i and not stop.is_set() \
                            and not state["failed"]:
                        gate.wait(0.05)
                    if stop.is_set() or state["failed"]:
                        return
                # this chunk's turn: the bounded put happens outside the
                # gate (it may block on a full queue), and successors
                # cannot pass until "next" advances below
                q.put(decoded)
                with gate:
                    state["next"] += 1
                    gate.notify_all()

        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"multiscope-decode-{k}")
                   for k in range(min(self.workers, max(len(tasks), 1)))]
        for th in threads:
            th.start()
        return q, threads, len(tasks), stop

    def cancel(self, ctx: _RunContext, handle) -> None:
        """Stop the decode workers and discard whatever they produced.
        A worker may be blocked in ``q.put`` on the full bounded queue,
        so keep consuming until every thread exits — a bare ``join``
        would deadlock.  (Gate waiters poll ``stop`` on a timeout.)"""
        q, threads, _, stop = handle
        stop.set()
        while any(th.is_alive() for th in threads):
            try:
                q.get(timeout=0.05)
            except queue.Empty:
                pass
        for th in threads:
            th.join()

    def drain(self, ctx: _RunContext, handle,
              stages: Dict[str, Callable]) -> None:
        q, threads, n, _ = handle
        try:
            for _ in range(n):
                item = q.get()
                if isinstance(item, _WorkerFailure):
                    raise item.exc
                task = item
                for name in STAGES[1:]:
                    task = stages[name](ctx, task)
        except BaseException:
            # a stage failed mid-stream: unblock the producers before
            # propagating, or a q.put on the full queue never returns
            self.cancel(ctx, handle)
            raise
        for th in threads:
            th.join()


class _PoolRun:
    """One run's state inside a shared ``DecodePool``: a bounded output
    queue plus a per-run reorder gate (chunks are admitted strictly in
    chunk order, whichever pool worker decoded them first)."""

    def __init__(self, ctx: "_RunContext", tasks: List[ChunkTask],
                 stages: Dict[str, Callable], depth: int):
        self.ctx = ctx
        self.tasks = tasks
        self.stages = stages
        self.q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self.gate = threading.Condition()
        self.next = 0               # chunk index admitted next
        self.remaining = len(tasks)  # jobs not yet enqueued or dropped
        self.failed = False
        self.cancelled = False

    def _account(self) -> None:
        with self.gate:
            self.remaining -= 1
            self.gate.notify_all()


class DecodePool:
    """Persistent decode workers shared by several in-flight runs.

    ``run_clips`` keeps (at most) two clips in flight; with per-run
    workers that is ``2 * decode_workers`` threads, churned on every
    clip boundary.  The pool owns ONE set of ``workers`` threads for
    its whole lifetime: each run submits its chunks as jobs on a shared
    FIFO, and a per-run reorder gate (``_PoolRun``) recovers chunk
    order before the bounded hand-off queue — so the draining thread,
    and with it TRACK, still sees every run's chunks strictly in frame
    order and tracks stay bit-identical to the dedicated-worker
    schedule for any pool size (tests/test_executor.py).

    Jobs of different runs interleave in submission order, which is
    exactly the decode order the two-in-flight ``run_clips`` loop
    wants: clip i's remaining chunks first, then clip i+1's.  A worker
    blocked on one run's full output queue parks with a timeout, so a
    ``cancel`` of that run (or its drain making progress) always
    releases it; cancelling a run drops its undecoded jobs on the floor
    as workers reach them.

    Discipline: runs sharing a pool must be DRAINED in submission order
    (or cancelled) — ``run_clips`` and the segment ingestor both do.  A
    later-submitted run drained first could starve behind an earlier
    run's full bounded queue that nobody is consuming.
    """

    def __init__(self, workers: int = 2):
        self.workers = max(1, int(workers))
        self._jobs: "queue.Queue" = queue.Queue()
        self._closed = False
        # /healthz backpressure signal: undecoded jobs on the shared
        # FIFO (qsize is advisory, which is all a health grade needs)
        self._m_queue_depth = REGISTRY.gauge(
            "executor.decode.queue_depth")
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"multiscope-pool-decode-{k}")
            for k in range(self.workers)]
        for th in self._threads:
            th.start()

    def submit(self, ctx: "_RunContext", tasks: List[ChunkTask],
               stages: Dict[str, Callable], depth: int) -> _PoolRun:
        if self._closed:
            # jobs enqueued after close would never run and the run's
            # drain would hang on an empty queue forever — fail fast
            raise RuntimeError("DecodePool is closed")
        run = _PoolRun(ctx, tasks, stages, depth)
        for i, task in enumerate(tasks):
            self._jobs.put((run, i, task))
        self._m_queue_depth.set(self._jobs.qsize())
        return run

    def cancel(self, run: _PoolRun) -> None:
        """Drop the run: undecoded jobs are discarded as workers reach
        them, and the output queue is drained so no shared worker stays
        blocked on it.  Returns once every job is accounted for."""
        with run.gate:
            run.cancelled = True
            run.gate.notify_all()
        while True:
            with run.gate:
                if run.remaining <= 0:
                    return
            try:
                run.q.get(timeout=0.02)
            except queue.Empty:
                pass

    def close(self) -> None:
        """Stop the workers (idempotent).  Outstanding runs must be
        drained or cancelled first."""
        if self._closed:
            return
        self._closed = True
        for _ in self._threads:
            self._jobs.put(None)
        for th in self._threads:
            th.join()

    # -- worker side ----------------------------------------------------------

    def _put(self, run: _PoolRun, item) -> None:
        while not run.cancelled:
            try:
                run.q.put(item, timeout=0.05)
                return
            except queue.Full:
                pass

    def _worker(self) -> None:
        while True:
            job = self._jobs.get()
            self._m_queue_depth.set(self._jobs.qsize())
            if job is None:
                return
            run, i, task = job
            try:
                self._decode_one(run, i, task)
            finally:
                run._account()

    def _decode_one(self, run: _PoolRun, i: int,
                    task: ChunkTask) -> None:
        if run.cancelled or run.failed:
            return                      # dropped job
        try:
            decoded = run.stages["decode"](run.ctx, task)
        except BaseException as exc:    # surfaced by drain()
            with run.gate:
                run.failed = True
                run.gate.notify_all()
            self._put(run, _WorkerFailure(exc))
            return
        with run.gate:
            while run.next != i and not run.cancelled and not run.failed:
                run.gate.wait(0.05)
            if run.cancelled or run.failed:
                return
        self._put(run, decoded)
        with run.gate:
            run.next += 1
            run.gate.notify_all()


class PooledStreamingScheduler:
    """The streaming schedule with decode on a shared ``DecodePool``
    instead of per-run threads.  Drain semantics (and therefore tracks)
    are identical to ``StreamingScheduler``."""

    def __init__(self, pool: DecodePool, depth: int = 2):
        self.pool = pool
        self.depth = max(1, int(depth))

    def start(self, ctx: "_RunContext", tasks: List[ChunkTask],
              stages: Dict[str, Callable]) -> _PoolRun:
        return self.pool.submit(ctx, tasks, stages, self.depth)

    def cancel(self, ctx: "_RunContext", run: _PoolRun) -> None:
        self.pool.cancel(run)

    def drain(self, ctx: "_RunContext", run: _PoolRun,
              stages: Dict[str, Callable]) -> None:
        try:
            for _ in range(len(run.tasks)):
                item = run.q.get()
                if isinstance(item, _WorkerFailure):
                    raise item.exc
                task = item
                for name in STAGES[1:]:
                    task = stages[name](ctx, task)
        except BaseException:
            # unblock any pool worker parked on this run's queue before
            # propagating (shared workers must outlive a failed run)
            self.pool.cancel(run)
            raise


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

@dataclass
class _ActiveRun:
    """A clip whose DECODE may already be running ahead."""
    ctx: _RunContext
    handle: object


class ClipExecutor:
    """Execute θ over clips through the stage graph.

    ``stages`` lets a caller swap any stage implementation (the
    pluggable part); ``options`` picks the scheduler and device
    placement.  ``start``/``finish`` expose the two-phase form so
    ``run_clips`` can overlap clip i+1's decode with clip i's compute.
    """

    def __init__(self, bank: ModelBank, params: PipelineParams,
                 options: Optional[ExecutorOptions] = None,
                 stages: Optional[Dict[str, Callable]] = None,
                 scheduler=None):
        self.bank = bank
        self.params = params
        self.options = options or ExecutorOptions()
        from repro.kernels import use_pallas
        if self.options.mesh is not None and use_pallas():
            raise NotImplementedError(
                "ExecutorOptions.mesh cannot run where window gathers are "
                "Pallas kernels (a TPU): Mosaic kernels are not "
                "partitioned automatically, and window_gather_batch has "
                "no shard_map over the chunk's batch axis with a "
                "per-shard window table.  Leave mesh unset to "
                "round-robin whole chunks over ExecutorOptions.devices.")
        self.stages = dict(DEFAULT_STAGES)
        if stages:
            self.stages.update(stages)
        self.stages = {name: _timed(name, fn)
                       for name, fn in self.stages.items()}
        if scheduler is not None:
            self.scheduler = scheduler
        elif self.options.decode_pool is not None and self.options.prefetch:
            self.scheduler = PooledStreamingScheduler(
                self.options.decode_pool, self.options.prefetch_depth)
        elif self.options.prefetch:
            self.scheduler = StreamingScheduler(
                self.options.prefetch_depth, self.options.decode_workers)
        else:
            self.scheduler = SequentialScheduler()

    def _tasks(self, ctx: _RunContext) -> List[ChunkTask]:
        ids = ctx.frame_ids
        return [ChunkTask(i, ids[c0:c0 + ctx.chunk])
                for i, c0 in enumerate(range(0, len(ids), ctx.chunk))]

    def start(self, clip: Clip, device_offset: int = 0, *,
              frame_ids: Optional[Sequence[int]] = None,
              tracker: Optional[object] = None) -> _ActiveRun:
        """Start a run.  ``frame_ids``/``tracker`` are the resume hooks
        used by the live-ingestion path (``repro.stream``): run only an
        explicit frame slice, feeding an existing tracker whose state
        carries across segment runs."""
        ctx = _RunContext(self.bank, self.params, clip, self.options,
                          device_offset=device_offset,
                          frame_ids=frame_ids, tracker=tracker)
        handle = self.scheduler.start(ctx, self._tasks(ctx), self.stages)
        return _ActiveRun(ctx, handle)

    def cancel(self, run: _ActiveRun) -> None:
        """Abandon a started run: stop its decode worker, drop its
        broker registration (pending broker requests are cancelled
        without affecting other streams) and release everything it
        buffered."""
        try:
            self.scheduler.cancel(run.ctx, run.handle)
        finally:
            run.ctx.close()

    def finish(self, run: _ActiveRun) -> RunResult:
        ctx = run.ctx
        t0 = time.process_time()
        try:
            self.scheduler.drain(ctx, run.handle, self.stages)
        except BaseException as exc:
            # black box: a no-op unless a FlightRecorder is installed
            crash_dump("executor.drain", exc,
                       extra={"stream": ctx.stream,
                              "frames": len(ctx.frame_ids),
                              "chunk": ctx.chunk})
            raise
        finally:
            ctx.close()
        tracks = ctx.tracker.result()
        if ctx.params.refine and ctx.bank.refiner is not None:
            tracks = [ctx.bank.refiner.refine(t) for t in tracks]
        seconds = time.process_time() - t0 + max(ctx.charged, 0.0)
        stage_seconds = ctx.profile.stage_seconds()
        track_disp = int(getattr(ctx.tracker, "dispatches", 0)) \
            - ctx._disp_track0 + ctx.profile.dispatches("embed")
        dispatches = {"proxy": ctx.profile.dispatches("proxy"),
                      "detect": ctx.profile.dispatches("detect"),
                      "track": track_disp}
        ctx.profile.disp["track"] = track_disp
        ctx.profile.publish()
        return RunResult(tracks, seconds, len(ctx.frame_ids),
                         ctx.n_windows, ctx.full_frames, ctx.skipped,
                         stage_seconds=stage_seconds,
                         dispatches=dispatches,
                         proxy_fracs=ctx.proxy_fracs)

    def run(self, clip: Clip) -> RunResult:
        return self.finish(self.start(clip))


def run_clip_streamed(bank: ModelBank, params: PipelineParams,
                      clip: Clip,
                      options: Optional[ExecutorOptions] = None
                      ) -> RunResult:
    """One clip through the streaming executor (prefetch on by
    default).  Tracks and counters are bit-identical to
    ``pipeline.run_clip_frames``."""
    return ClipExecutor(bank, params, options).run(clip)


def run_clips(bank: ModelBank, params: PipelineParams,
              clips: Sequence[Clip],
              options: Optional[ExecutorOptions] = None
              ) -> Tuple[List[RunResult], float]:
    """Multi-clip sweep (the experiment driver's test-split loop).

    Clips are independent through DETECT, so with prefetch enabled clip
    i+1's decode workers are started while clip i is still draining, and
    each clip's chunks round-robin the device list from a per-clip
    offset — on a multi-device mesh, consecutive clips land on
    different devices.  With ``options.share_decode_pool`` (the
    default) the two in-flight clips share ONE ``DecodePool`` of
    ``max(2, decode_workers)`` persistent workers with per-clip reorder
    gates — no thread churn at clip boundaries, and total decode
    threads are the pool size rather than ``2 * decode_workers``
    (tracks stay bit-identical for any pool size; an
    ``options.decode_pool`` supplied by the caller is reused as-is and
    left open).  TRACK state never crosses clips, and per-clip seconds
    keep the process-time + ledger semantics (decode CPU spent early is
    counted once, in whichever window it ran)."""
    opts = options or ExecutorOptions()
    own_pool: Optional[DecodePool] = None
    if opts.prefetch and len(clips) > 1 and opts.share_decode_pool \
            and opts.decode_pool is None:
        own_pool = DecodePool(max(2, opts.decode_workers))
        import dataclasses as _dc
        opts = _dc.replace(opts, decode_pool=own_pool)
    ex = ClipExecutor(bank, params, opts)
    results: List[RunResult] = []
    try:
        if not opts.prefetch or len(clips) <= 1:
            for i, clip in enumerate(clips):
                results.append(ex.finish(ex.start(clip, device_offset=i)))
            return results, sum(r.seconds for r in results)
        pending: List[_ActiveRun] = [ex.start(clips[0], device_offset=0)]
        try:
            for i in range(1, len(clips)):
                # one clip of decode lookahead: prefetch_depth chunks max
                pending.append(ex.start(clips[i], device_offset=i))
                results.append(ex.finish(pending.pop(0)))
            results.append(ex.finish(pending.pop(0)))
        except BaseException:
            # the failed clip's own worker was stopped by drain; clips
            # started ahead still have live workers that would otherwise
            # block forever holding decoded chunks and device buffers
            for run in pending:
                ex.cancel(run)
            raise
        return results, sum(r.seconds for r in results)
    finally:
        if own_pool is not None:
            own_pool.close()
