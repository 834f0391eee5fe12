"""Single-token (decode) GQA attention as a Pallas TPU kernel.

Decode attention is MEMORY-bound: every step sweeps the whole KV cache from
HBM and does O(S*D) FLOPs per head — arithmetic intensity ~1 FLOP/byte, far
below the v5e ridge (~240), so the kernel's only job is to stream K/V at
full HBM bandwidth and avoid materializing repeated GQA heads.

Tiling:
  grid = (B, Hkv, S/bk) with the KV axis SEQUENTIAL; the GQA q-group (G =
  Hq/Hkv) is packed into the MXU M dimension: q block (G, D) x k block
  (bk, D)^T -> (G, bk) scores.  bk = 512 amortizes the per-block overhead
  over a deep HBM stream.  kv_len lives in SMEM (one scalar per batch row)
  and masks the ragged tail block; pl.when skips FLOPs for fully-invalid
  blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = float(jnp.finfo(jnp.float32).min)


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, sm_scale: float, bk: int, kv_blocks: int):
    ki = pl.program_id(2)
    kv_len = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ki * bk

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)         # (G, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)         # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)         # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (G, bk)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_k",
                                             "interpret"))
def decode_attention_pallas(q, k, v, kv_len, *, sm_scale=None,
                            block_k: int = 512, interpret: bool = False):
    """q: (B, Hq, D); k, v: (B, S, Hkv, D); kv_len: (B,) -> (B, Hq, D)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    bk = min(block_k, S)
    assert S % bk == 0
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    kv_blocks = S // bk
    grid = (B, Hkv, kv_blocks)
    qg = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(_dec_kernel, sm_scale=float(sm_scale),
                               bk=bk, kv_blocks=kv_blocks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ki: (b,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, ki: (b, ki, h, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, ki: (b, ki, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name="decode_attention",
    )(kv_len.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, D)
