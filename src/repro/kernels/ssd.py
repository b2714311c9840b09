"""Pallas TPU kernel for the Mamba2 SSD chunk scan.

TPU adaptation of the SSD "hardware-efficient dual form" (arXiv:2405.21060):
the grid is (batch, heads, chunks) with the chunk dimension innermost and
sequential; the inter-chunk SSM state (N x P) lives in VMEM scratch and is
carried across grid steps, so HBM traffic is exactly one read of the inputs
and one write of the outputs.  Inside a chunk the computation is three
MXU matmuls: (Q x Q) intra-chunk attention-like scores, (Q x N)·(N x P)
inter-chunk contribution, and the chunk-state update.

Shapes (pre-repeated across the group dim by ops.py):
  x  (B, H, L, P)    dt (B, H, L)     a (H,) negative
  bmat, cmat (B, H, L, N)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    def init_state():
        state_ref[...] = jnp.zeros_like(state_ref)

    jax.lax.cond(ci == 0, init_state, lambda: None)

    a = a_ref[0]                                      # scalar decay rate
    x = x_ref[0, 0].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)             # (Q, 1)
    bm = b_ref[0, 0].astype(jnp.float32)              # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)              # (Q, N)

    adt = dt * a                                      # (Q, 1)
    cum = jnp.cumsum(adt, axis=0)                     # (Q, 1)

    # intra-chunk: att[i, j] = (c_i . b_j) exp(cum_i - cum_j) dt_j, j <= i
    seg = cum - cum.T                                 # (Q, Q) = cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj
    decay = jnp.where(causal, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    att = scores * decay * dt.T                       # (Q, Q)
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += (c_i exp(cum_i)) . S_prev
    y = y + jax.lax.dot_general(cm * jnp.exp(cum), state_ref[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: S = S exp(cum_last) + sum_j exp(cum_last - cum_j) dt_j b_j x_j^T
    tail = jnp.exp(cum[-1:] - cum) * dt               # (Q, 1)
    state_ref[...] = (state_ref[...] * jnp.exp(cum[-1])
                      + jax.lax.dot_general(bm * tail, x,
                                            (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, bmat: jnp.ndarray,
        cmat: jnp.ndarray, chunk: int = 128,
        interpret: bool = False) -> jnp.ndarray:
    """Chunked SSD scan.  Returns y (B, H, L, P) in float32."""
    b, h, l, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, l)
    pad = (-l) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
        bmat = jnp.pad(bmat, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // q
    dt4 = dt[..., None]                               # (B, H, Lp, 1)

    grid = (b, h, nc)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h_, c_: (h_,)),
            pl.BlockSpec((1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p),
                               lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lp, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x, dt4, bmat, cmat)
    return out[:, :, :l]
