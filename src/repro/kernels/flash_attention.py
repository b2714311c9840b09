"""Pallas TPU flash-attention (forward) kernel.

Canonical TPU pattern: grid (batch, heads, q_blocks, kv_blocks); the kv
dimension is innermost and iterated sequentially per core, accumulating the
online softmax state (m, l, acc) in VMEM scratch.  Block shapes are
hardware-aligned: q/kv block sizes default to 128/256 (multiples of the
8x128 VREG tile and the 128x128 MXU), and the head dim rides whole.

GQA is handled in the k/v index_map (query head h reads kv head h // rep),
so K/V are never materialized repeated.

Validated against kernels/flash_attention_ref.py in interpret mode on CPU
(tests/test_kernels.py) — the TPU is the *target*, not the runtime.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bkv: int,
                  seq_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nkv = pl.num_programs(3)

    # (re)initialize scratch at the first kv block of every q block
    def init_scratch():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    jax.lax.cond(ki == 0, init_scratch, lambda: None)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = kpos < seq_kv
    if causal:
        ok = ok & (kpos <= qpos)
    if window > 0:
        ok = ok & (qpos - kpos < window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                           # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    def finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    jax.lax.cond(ki == nkv - 1, finalize, lambda: None)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Sq, D); k, v: (B, KV, Skv, D).  Returns (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    kv, skv = k.shape[1], k.shape[2]
    rep = h // kv
    scale = d ** -0.5
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    pq, pkv = (-sq) % bq, (-skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    nq, nkv = (sq + pq) // bq, (skv + pkv) // bkv

    grid = (b, h, nq, nkv)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, seq_kv=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, qi, ki: (b_, h_ // rep, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, qi, ki: (b_, h_ // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq + pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denominator
            pltpu.VMEM((bq, d), jnp.float32),     # running accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
