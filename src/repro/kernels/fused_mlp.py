"""Pallas TPU kernel: fused inference of the Habitat MLP predictors.

The paper's predictors are 8x1024 ReLU MLPs (Sec. 3.4).  Serving them
per-op during trace prediction is a chain of tiny matmuls that would
round-trip HBM after every layer; this kernel keeps the activations
resident in VMEM and streams one (H x H) weight block per sequential grid
step, so HBM traffic is weights-once + inputs/outputs-once.

Layout: all layers are padded to a uniform hidden size H (the input block
is zero-padded, the scalar output is column 0 of the last layer), giving
weights (L, H, H) and biases (L, H).

  grid = (batch_blocks, layers)   # layers innermost, sequential
  scratch h: (bm, H) VMEM, initialized from x at l == 0,
  ReLU between layers, written to out at l == L-1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlp_kernel(x_ref, w_ref, b_ref, o_ref, h_ref):
    li = pl.program_id(1)
    nl = pl.num_programs(1)

    def init():
        h_ref[...] = x_ref[0].astype(jnp.float32)

    jax.lax.cond(li == 0, init, lambda: None)

    w = w_ref[0].astype(jnp.float32)                 # (H, H)
    b = b_ref[0].astype(jnp.float32)                 # (1, H)
    z = jax.lax.dot_general(h_ref[...], w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + b
    h_ref[...] = jnp.where(li == nl - 1, z, jax.nn.relu(z))

    def finalize():
        o_ref[0] = h_ref[...].astype(o_ref.dtype)

    jax.lax.cond(li == nl - 1, finalize, lambda: None)


def fused_mlp(x: jnp.ndarray, weights: jnp.ndarray, biases: jnp.ndarray,
              block_m: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x (B, H), weights (L, H, H), biases (L, H) -> (B,) (= column 0).

    The caller pads the first layer's input columns and the last layer's
    output columns with zeros (see ops.pack_mlp_params)."""
    bsz, hdim = x.shape
    nl = weights.shape[0]
    bm = min(block_m, bsz)
    pad = (-bsz) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nb = (bsz + pad) // bm

    out = pl.pallas_call(
        _mlp_kernel,
        grid=(nb, nl),
        in_specs=[
            pl.BlockSpec((1, bm, hdim),
                         lambda bi, li: (0, bi, 0)),
            pl.BlockSpec((1, hdim, hdim), lambda bi, li: (li, 0, 0)),
            pl.BlockSpec((1, 1, hdim), lambda bi, li: (li, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, hdim), lambda bi, li: (0, bi, 0)),
        out_shape=jax.ShapeDtypeStruct((1, bsz + pad, hdim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, hdim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x[None], weights, biases[:, None, :])
    return out[0, :bsz, 0]
