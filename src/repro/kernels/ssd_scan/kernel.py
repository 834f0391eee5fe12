"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

The SSD insight: the linear recurrence over a CHUNK of Q tokens can be
rewritten as dense matmuls (MXU work) plus a tiny sequential state carry
between chunks:

  intra:  y  = [(C @ B^T) .* decay_mask] @ (dt * x)        (Q,Q)@(Q,P)
  inter:  y += exp(L) .* (C @ state^T)                      (Q,N)@(N,P)
  carry:  state = exp(L_Q) * state + (x * w)^T @ B          (P,Q)@(Q,N)

with L the within-chunk cumulative log-decay and w_j = exp(L_Q - L_j)*dt_j.

Tiling: grid = (B, H, S/Q) with the chunk axis SEQUENTIAL; the (P, N) fp32
state lives in VMEM scratch and carries across chunks.  Q = chunk 128 and
P, N multiples of 8 keep all three matmuls MXU-aligned.  B/C are shared
across heads (n_groups = 1): their blocks are indexed by (b, c) only, so
Mosaic re-fetches them once per head sweep rather than per (head, chunk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, fin_ref, state_ref, *, Q: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, :, 0:1].astype(jnp.float32)       # (Q, 1) — see ops.py
    Bc = b_ref[0, :, :].astype(jnp.float32)          # (Q, N)
    Cc = c_ref[0, :, :].astype(jnp.float32)          # (Q, N)
    A = a_ref[0]                                     # scalar (SMEM)
    Dh = d_ref[0]

    s = dt * A                                       # (Q, 1) log-decays
    L = jnp.cumsum(s, axis=0)                        # (Q, 1)
    # decay_mask[t, j] = exp(L_t - L_j) for j <= t else 0
    diff = L - L.reshape(1, Q)                       # (Q, Q)
    ti = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ji = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = ji <= ti
    decay = jnp.where(mask, jnp.exp(diff), 0.0)

    G = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    G = G * decay
    xdt = x * dt                                      # (Q, P)
    y = jax.lax.dot_general(G, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)

    state = state_ref[...]                            # (P, N)
    y += jnp.exp(L) * jax.lax.dot_general(
        Cc, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (Q, P)
    y += Dh * x
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state carry
    LQ = L[Q - 1]                                     # scalar-ish (1,)
    w = jnp.exp(LQ - L) * dt                          # (Q, 1)
    state_new = jnp.exp(LQ) * state + jax.lax.dot_general(
        x * w, Bc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (P, N)
    state_ref[...] = state_new

    @pl.when(ci == n_chunks - 1)
    def _fin():
        fin_ref[0, 0, :, :] = state_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B, C, D, *, chunk: int = 128,
                    interpret: bool = False):
    """x: (b, S, H, P); dt: (b, S, H); A, D: (H,); B, C: (b, S, N).

    Returns (y (b,S,H,P), final_state (b,H,P,N) fp32).
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    n_chunks = S // Q
    grid = (b, H, n_chunks)

    kernel = functools.partial(_ssd_kernel, Q=Q, n_chunks=n_chunks)
    y, fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda bi, h, c: (bi, c, h, 0)),
            pl.BlockSpec((1, Q, 1), lambda bi, h, c: (bi, c, h)),
            pl.BlockSpec((1,), lambda bi, h, c: (h,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, N), lambda bi, h, c: (bi, c, 0)),
            pl.BlockSpec((1, Q, N), lambda bi, h, c: (bi, c, 0)),
            pl.BlockSpec((1,), lambda bi, h, c: (h,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda bi, h, c: (bi, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, c: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt.astype(jnp.float32), A.astype(jnp.float32), B, C,
      D.astype(jnp.float32))
    return y, fin
