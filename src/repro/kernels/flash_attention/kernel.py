"""Flash attention as a Pallas TPU kernel.

Tiling (TPU target):
  grid = (B, Hq, Sq/bq, Skv/bk); the last axis is SEQUENTIAL (ARBITRARY)
  so the online-softmax accumulators (m, l, acc) live in VMEM scratch and
  carry across KV blocks.  Q block (bq, D) stays resident in VMEM for the
  whole KV sweep; K/V stream through in (bk, D) blocks.  bq = bk = 128 keeps
  the two matmuls MXU-shaped (128 x D x 128).  GQA is expressed in the K/V
  index_map (kv head = q head // group) so K/V blocks are fetched once per
  q-head-group position rather than materializing repeated heads in HBM.

Causal skipping: blocks strictly above the diagonal contribute nothing; we
gate the FLOPs with pl.when (the block DMA for skipped blocks is still
issued by the pipeline — at most a 2x bandwidth overhead on the strictly
upper triangle and zero wasted MXU time; the ops.py wrapper additionally
shrinks the grid when Sq == Skv so fully-masked tiles are never visited).

VMEM budget per step: q(bq*D) + k,v(2*bk*D) + acc(bq*D fp32) + out(bq*D)
= at D=128, bq=bk=128: ~64 KiB*3 + 64 KiB + 64 KiB ≈ 320 KiB (double-
buffered K/V adds 2*64 KiB) — far inside the ~16 MiB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = float(jnp.finfo(jnp.float32).min)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               sm_scale: float, causal: bool, bq: int, bk: int,
               kv_blocks: int, sq: int, skv: int, kv_valid: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (queries sit at the END of the kv axis when Sq<Skv)
    q_start = qi * bq + (skv - sq)
    k_start = ki * bk

    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)        # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)        # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)        # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        if kv_valid:
            kpos2 = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos2 < kv_valid, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # skip blocks entirely above the diagonal
        pl.when(k_start <= q_start + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> 0 output
        o_ref[0, :, 0, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "block_q", "block_k",
                                             "interpret", "kv_valid"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, sm_scale=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False, kv_valid: int = 0):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    kv_blocks = Skv // bk
    grid = (B, Hq, Sq // bq, kv_blocks)

    kernel = functools.partial(
        _fa_kernel, sm_scale=float(sm_scale), causal=causal, bq=bq, bk=bk,
        kv_blocks=kv_blocks, sq=Sq, skv=Skv, kv_valid=kv_valid)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, bk, 1, D),
                         lambda b, h, qi, ki: (b, ki, h // group, 0)),
            pl.BlockSpec((1, bk, 1, D),
                         lambda b, h, qi, ki: (b, ki, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, D),
                               lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
            pltpu.VMEM((bq, D), jnp.float32),    # fp32 accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
