"""Pallas TPU kernels for the perf-critical compute hot spots.

Each kernel subpackage follows the same layout:

  kernel.py — ``pl.pallas_call`` + explicit ``BlockSpec`` VMEM tiling,
              written for the TPU target (MXU-aligned tiles, sequential
              grid axes for accumulation).
  ops.py    — the public jit'd wrapper.  Dispatches to the Pallas kernel
              on TPU and to a memory-equivalent pure-jnp implementation on
              CPU, so models lower identically everywhere.  An op whose
              kernel body cannot be lowered for the TPU says so in a named
              ``TPU_PALLAS = False`` constant and runs its XLA
              implementation there.
  ref.py    — the pure-jnp oracle used by tests (``interpret=True`` runs
              the kernel body on CPU and is asserted allclose against it).

Kernels:
  flash_attention — block-tiled online-softmax causal attention (prefill).
  decode_attention — single-token GQA attention over a long KV cache.
  ssd_scan — Mamba2 state-space-duality chunked scan.
  proxy_score — the paper's proxy head: fused 1x1-conv + sigmoid +
                threshold producing the binary cell grid.
  window_gather — the paper's spatial skipping: gather 32-aligned windows
                  from a frame via a scalar-prefetched window table;
                  window_gather_batch gathers one size class across a
                  CHUNK of frames (the chunked engine's hot path).
  proxy_plan — fused proxy head + threshold + detector-grid mapping:
               emits the mapped positive-cell grid and per-frame plan
               stats (count + bbox) on-device, so only plan-sized
               tensors cross back to the host instead of score maps.
  assign — batched Hungarian assignment (Jonker-Volgenant shortest
           augmenting path), one (N, N) cost matrix per grid row;
           mirrors ``core.hungarian._hungarian_np`` including
           first-index tie-breaking.
"""
from __future__ import annotations

import jax

_FORCE: dict = {"mode": None}   # None=auto | "pallas" | "ref"


def set_kernel_mode(mode) -> None:
    """Force kernel dispatch: None (auto), 'pallas', or 'ref'."""
    assert mode in (None, "pallas", "ref")
    _FORCE["mode"] = mode


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas(tpu_pallas: bool = True) -> bool:
    """Whether an op runs its Pallas kernel.  ``tpu_pallas`` is the op's
    named per-kernel choice for the TPU: False sends its TPU calls to
    the op's XLA implementation (a kernel body Mosaic cannot lower),
    while a forced "pallas" mode still runs the kernel, interpreted,
    off the TPU."""
    if _FORCE["mode"] == "pallas":
        return True
    if _FORCE["mode"] == "ref":
        return False
    return tpu_pallas and on_tpu()


def interpret() -> bool:
    """Run Pallas kernels under the interpreter: only off the TPU.  On a
    TPU every Pallas call compiles; nothing falls back."""
    return not on_tpu()
