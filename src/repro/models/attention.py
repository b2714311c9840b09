"""Attention: GQA with RoPE, qk-norm, sliding windows; flash (chunked) and
dense paths; decode path against a KV cache.

The chunked path is an online-softmax (flash) algorithm in pure jnp: it
never materializes the full (Sq, Skv) score matrix, which is what makes the
prefill_32k shapes compile within per-device memory.  It doubles as the
oracle for the Pallas flash kernel (kernels/flash_attention.py).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(qpos, kpos, causal: bool, window) -> jnp.ndarray:
    """(…, q, k) boolean mask. window is 0 (off) or a traced/int scalar."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        ok = ok & (k <= q)
    ok = ok & jnp.where(window > 0, (q - k) < window, True)
    return ok


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0,
                    q_offset: int = 0,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Reference attention materializing the score matrix.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H = KV * rep.
    ``scale`` multiplies the scores (default 1/sqrt(hd))."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    rep = h // kv
    scale = hd ** -0.5 if scale is None else scale
    qr = q.reshape(b, sq, kv, rep, hd).astype(jnp.float32)
    s = jnp.einsum("bqkrd,bskd->bkrqs", qr, k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(skv)
    m = _mask(qpos, kpos, causal, window)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkrqs,bskd->bqkrd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0,
                    q_offset: int = 0, chunk_q: int = 1024,
                    chunk_kv: int = 1024,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax chunked attention (never materializes Sq x Skv).

    Block-sparsity (§Perf hillclimb #2): q chunks iterate in a *python*
    loop, so each chunk's kv scan statically covers only blocks inside the
    causal triangle — fully-masked future blocks are never built.  If
    ``window`` is a static python int > 0, past blocks outside the sliding
    window are statically skipped too (banded attention: O(S·W) instead of
    O(S²) — this is what makes gemma3's 5 local layers per global layer
    cheap at 32k).  ``scale`` as in :func:`dense_attention`."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    rep = h // kv
    scale = hd ** -0.5 if scale is None else scale
    cq = min(chunk_q, sq)
    ckv = min(chunk_kv, skv)
    # pad to multiples
    pq = (-sq) % cq
    pkv = (-skv) % ckv
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    nq, nkv = (sq + pq) // cq, (skv + pkv) // ckv
    qr = (qp.reshape(b, nq, cq, kv, rep, hd)
          .transpose(1, 0, 3, 4, 2, 5))          # (nq, B, KV, rep, cq, hd)
    kr = kp.reshape(b, nkv, ckv, kv, hd).transpose(1, 0, 3, 2, 4)
    vr = vp.reshape(b, nkv, ckv, kv, hd).transpose(1, 0, 3, 2, 4)
    static_window = isinstance(window, int) and window > 0

    def kv_block_fn(qb, qpos):
        def kv_block(carry, inp):
            ki, kb, vb = inp
            m_run, l_run, acc = carry
            kpos = ki * ckv + jnp.arange(ckv)
            s = jnp.einsum("bkrqd,bksd->bkrqs", qb,
                           kb.astype(jnp.float32)) * scale
            ok = _mask(qpos, kpos, causal, window) & (kpos < skv)[None, :]
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkrqs,bksd->bkrqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc), None
        return kv_block

    outs = []
    for qi in range(nq):  # static: enables causal/banded block skipping
        qb = qr[qi].astype(jnp.float32)
        qpos = q_offset + qi * cq + jnp.arange(cq)
        # static kv block range for this q chunk
        hi = nkv
        if causal and q_offset == 0:
            hi = min(nkv, ((qi + 1) * cq + ckv - 1) // ckv)
        lo = 0
        if static_window and causal and q_offset == 0:
            lo = max(0, (qi * cq - window) // ckv)
        init = (jnp.full((b, kv, rep, cq), NEG_INF, jnp.float32),
                jnp.zeros((b, kv, rep, cq), jnp.float32),
                jnp.zeros((b, kv, rep, cq, hd), jnp.float32))
        (m_run, l_run, acc), _ = jax.lax.scan(
            kv_block_fn(qb, qpos), init,
            (jnp.arange(lo, hi), kr[lo:hi], vr[lo:hi]))
        outs.append(acc / jnp.maximum(l_run, 1e-30)[..., None])

    out = jnp.stack(outs)                         # (nq, B, KV, rep, cq, hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * cq, h, hd)
    return out[:, :sq].astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, index: jnp.ndarray,
                     window: int = 0) -> jnp.ndarray:
    """Single-token attention against a (possibly sharded) KV cache.

    q: (B, 1, H, hd); caches: (B, S, KV, hd); index: (B,) per-slot
    positions (continuous batching: every slot has its own length)."""
    b, _, h, hd = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    rep = h // kv
    scale = hd ** -0.5
    qr = q.reshape(b, kv, rep, hd).astype(jnp.float32)
    logits = jnp.einsum("bkrd,bskd->bkrs", qr,
                        k_cache.astype(jnp.float32)) * scale
    kpos = jnp.arange(s)[None, :]
    idx = index[:, None]
    ok = (kpos <= idx) & jnp.where(window > 0, (idx - kpos) < window, True)
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrs,bskd->bkrd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)
