"""Pallas TPU kernel: one fused launch scoring ALL op-kind MLPs.

The fleet engine (``core/batched.py``) prices kernel-varying ops with one
pre-trained MLP per op kind (conv2d / linear / bmm / recurrent).  The
single-trace path issues one jitted forward per kind — four launches per
prediction, each a chain of small matmuls.  The ragged multi-trace sweep
replaces them with ONE launch over the whole device-major feature grid:
rows are grouped by op kind and padded to whole batch blocks, and a
scalar-prefetched block->kind map selects which MLP's weight stack each
block flows through.

Layout mirrors ``fused_mlp.py`` but adds a leading kind axis:

  weights (K, L, H, H), biases (K, L, H)   -- all kinds' layers, padded to
                                              one uniform hidden size H
  x       (B, H)                           -- B = n_blocks * block_m rows
  block_kinds (n_blocks,) int32            -- scalar prefetch: kind of the
                                              MLP scoring each row block

  grid = (batch_blocks, layers)            -- layers innermost, sequential
  scratch h: (bm, H) VMEM, initialized from x at l == 0, ReLU between
  layers, written to out at l == L-1; the prediction is column 0.

The weight BlockSpec index map reads ``block_kinds[bi]`` — consecutive
blocks with the same kind reuse the resident weight block, so sorting rows
by kind (the engine always does) keeps weight traffic at one (L, H, H)
stream per distinct kind, not per block.

Row-mapped variant (:func:`fused_mlp_score_rows`): rows carry their OWN
kind (``row_kinds (B,) int32``) instead of belonging to uniform-kind
blocks, so callers with arbitrary kind mixes — the cell-masked pair path,
whose cold cells interleave kinds — score everything in one launch with
no per-kind grouping or per-kind block padding.  The grid grows a kind
axis, ``(batch_blocks, layers, kinds)``, and two scalar-prefetched maps
derived from ``row_kinds`` keep it cheap:

  * ``match_kinds (nb, K)`` — kind k at step (bi, li, k), or -1 when no
    row of block ``bi`` has kind k: the whole step's compute is skipped
    (``pl.when``), so a kind-uniform block costs one matmul per layer,
    exactly like the block-mapped kernel;
  * ``dma_kinds (nb, K)`` — the weight-stack index actually fetched at
    each step; absent kinds repeat the nearest resident kind so the
    skipped steps re-use the resident weight block instead of streaming
    weights nobody multiplies.

Present kinds accumulate ``z += where(row_kind == k, h @ W_k + b_k, 0)``
into a VMEM scratch; each row has exactly one matching kind, so the
masked sum is exact (adding zeros), not an approximation.

Both kernels multiply at ``Precision.HIGHEST``: a TPU's default lowers a
float32 product to one bfloat16 pass, whose 8-bit mantissa would be
expected to move a predicted time by far more than the 1e-4 relative to
a float64 forward that the scorer is held to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def bucket_blocks(n_blocks: int) -> int:
    """Pad a row-block count to its jit bucket.

    ``fused_mlp_score`` is jitted per (batch, block_m) shape; coalesced
    service batches arrive at arbitrary sizes, so without bucketing every
    distinct batch recompiles the scorer.  Buckets are powers of two up
    to 32 blocks and multiples of 32 beyond — O(log) compiled shapes,
    padding waste bounded at 2x for tiny batches and ~3% at scale.
    Padding blocks must carry kind 0 and zero rows; their outputs are
    garbage by contract and callers slice them off.

    Contract at the edges: ``bucket_blocks(0) == 0`` — an empty batch
    stays empty (callers must not launch a zero-block kernel at all, and
    the engine never does: every scoring path guards on having rows) —
    and a negative count raises ``ValueError``."""
    n_blocks = int(n_blocks)
    if n_blocks < 0:
        raise ValueError(f"n_blocks must be >= 0, got {n_blocks}")
    if n_blocks == 0:
        return 0
    if n_blocks <= 32:
        return 1 << max(n_blocks - 1, 0).bit_length()
    return -(-n_blocks // 32) * 32


def bucket_rows(n_rows: int) -> int:
    """Pad a row count to its jit bucket (the stacked CPU lowering).

    Same shape-count policy as ``TrainedMLP.predict_ms``: powers of two
    up to 512 rows, multiples of 512 beyond — so the per-kind row depth
    of a stacked scorer batch compiles O(log) shapes.  Shares
    ``bucket_blocks``'s edge contract: 0 stays 0, negative raises."""
    n_rows = int(n_rows)
    if n_rows < 0:
        raise ValueError(f"n_rows must be >= 0, got {n_rows}")
    if n_rows == 0:
        return 0
    if n_rows <= 512:
        return 1 << max(n_rows - 1, 0).bit_length()
    return -(-n_rows // 512) * 512


def _score_kernel(kinds_ref, x_ref, w_ref, b_ref, o_ref, h_ref):
    del kinds_ref  # consumed by the BlockSpec index maps
    li = pl.program_id(1)
    nl = pl.num_programs(1)

    def init():
        h_ref[...] = x_ref[0].astype(jnp.float32)

    jax.lax.cond(li == 0, init, lambda: None)

    w = w_ref[0, 0].astype(jnp.float32)              # (H, H)
    b = b_ref[0, 0].astype(jnp.float32)              # (1, H)
    z = jax.lax.dot_general(h_ref[...], w, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32) + b
    h_ref[...] = jnp.where(li == nl - 1, z, jax.nn.relu(z))

    def finalize():
        o_ref[0] = h_ref[...].astype(o_ref.dtype)

    jax.lax.cond(li == nl - 1, finalize, lambda: None)


def fused_mlp_score(x: jnp.ndarray, block_kinds: jnp.ndarray,
                    weights: jnp.ndarray, biases: jnp.ndarray,
                    block_m: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """x (B, H); block_kinds (B // block_m,); weights (K, L, H, H);
    biases (K, L, H) -> (B,) (= column 0 of the last layer).

    ``B`` must already be a whole number of ``block_m`` blocks and every
    row of block ``i`` must belong to kind ``block_kinds[i]`` — the engine
    (``core.batched.FusedMLPScorer``) does the grouping and padding."""
    bsz, hdim = x.shape
    nb = block_kinds.shape[0]
    if nb * block_m != bsz:
        raise ValueError(f"x rows ({bsz}) != blocks x block_m "
                         f"({nb} x {block_m})")
    nl = weights.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nl),
        in_specs=[
            pl.BlockSpec((1, block_m, hdim),
                         lambda bi, li, kref: (0, bi, 0)),
            pl.BlockSpec((1, 1, hdim, hdim),
                         lambda bi, li, kref: (kref[bi], li, 0, 0)),
            pl.BlockSpec((1, 1, 1, hdim),
                         lambda bi, li, kref: (kref[bi], li, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, hdim),
                               lambda bi, li, kref: (0, bi, 0)),
        scratch_shapes=[pltpu.VMEM((block_m, hdim), jnp.float32)],
    )
    out = pl.pallas_call(
        _score_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, bsz, hdim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_kinds.astype(jnp.int32), x[None], weights,
      biases[:, :, None, :])
    return out[0, :, 0]


def _score_rows_kernel(dma_ref, match_ref, kinds_ref, x_ref, w_ref, b_ref,
                       o_ref, h_ref, z_ref):
    del dma_ref  # consumed by the weight/bias BlockSpec index maps
    bi = pl.program_id(0)
    li = pl.program_id(1)
    ki = pl.program_id(2)
    nl = pl.num_programs(1)
    nk = pl.num_programs(2)

    def init():
        h_ref[...] = x_ref[0].astype(jnp.float32)

    jax.lax.cond((li == 0) & (ki == 0), init, lambda: None)

    def zero():
        z_ref[...] = jnp.zeros_like(z_ref)

    jax.lax.cond(ki == 0, zero, lambda: None)

    kind = match_ref[bi, ki]

    def accumulate():
        # rows of this kind pick up their layer term; every other row adds
        # an exact 0.0, so the k-axis sum selects (not approximates) the
        # per-row weight stack
        w = w_ref[0, 0].astype(jnp.float32)              # (H, H)
        b = b_ref[0, 0].astype(jnp.float32)              # (1, H)
        z = jax.lax.dot_general(h_ref[...], w, (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32) + b
        mask = kinds_ref[...] == kind                    # (bm, 1)
        z_ref[...] += jnp.where(mask, z, 0.0)

    # kind == -1: no row of this block has kind ki — skip the matmul (the
    # resident weight block was a no-op re-fetch via dma_kinds)
    jax.lax.cond(kind >= 0, accumulate, lambda: None)

    def finalize_layer():
        h_ref[...] = jnp.where(li == nl - 1, z_ref[...],
                               jax.nn.relu(z_ref[...]))

    jax.lax.cond(ki == nk - 1, finalize_layer, lambda: None)

    def write_out():
        o_ref[0] = h_ref[...].astype(o_ref.dtype)

    jax.lax.cond((li == nl - 1) & (ki == nk - 1), write_out, lambda: None)


def _row_kind_maps(row_kinds: jnp.ndarray, n_blocks: int, block_m: int,
                   n_kinds: int):
    """(dma_kinds, match_kinds), both (n_blocks, n_kinds) int32.

    ``match_kinds[bi, k]`` is k when block ``bi`` holds at least one row
    of kind k, else -1 (step skipped).  ``dma_kinds[bi, k]`` is the
    weight stack fetched at that step: present kinds fetch themselves;
    absent kinds repeat the nearest present kind at or below k (or the
    block's first present kind), so consecutive skipped steps keep the
    resident weight block instead of streaming unused weights."""
    kinds = row_kinds.reshape(n_blocks, block_m)
    ks = jnp.arange(n_kinds, dtype=jnp.int32)
    present = (kinds[:, :, None] == ks[None, None, :]).any(axis=1)
    match = jnp.where(present, ks[None, :], jnp.int32(-1))
    below = jax.lax.cummax(match, axis=1)       # nearest present <= k
    first = jnp.argmax(present, axis=1).astype(jnp.int32)
    dma = jnp.where(below >= 0, below, first[:, None])
    return dma.astype(jnp.int32), match


def fused_mlp_score_rows(x: jnp.ndarray, row_kinds: jnp.ndarray,
                         weights: jnp.ndarray, biases: jnp.ndarray,
                         block_m: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """x (B, H); row_kinds (B,) int32; weights (K, L, H, H);
    biases (K, L, H) -> (B,) (= column 0 of the last layer).

    The row-mapped spelling of :func:`fused_mlp_score`: row ``i`` flows
    through MLP ``row_kinds[i]``, so callers need no per-kind grouping
    and no per-kind block padding — ONE launch for any kind mix.  ``B``
    must be a whole number of ``block_m`` blocks; padding rows must carry
    a valid kind (the engine uses 0) and their outputs are garbage by
    contract."""
    bsz, hdim = x.shape
    if row_kinds.shape != (bsz,):
        raise ValueError(f"row_kinds shape {row_kinds.shape} != ({bsz},)")
    if bsz % block_m:
        raise ValueError(f"x rows ({bsz}) not a multiple of block_m "
                         f"({block_m})")
    nb = bsz // block_m
    nk, nl = weights.shape[0], weights.shape[1]
    row_kinds = row_kinds.astype(jnp.int32)
    dma, match = _row_kind_maps(row_kinds, nb, block_m, nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, nl, nk),
        in_specs=[
            pl.BlockSpec((block_m, 1),
                         lambda bi, li, ki, dref, mref: (bi, 0)),
            pl.BlockSpec((1, block_m, hdim),
                         lambda bi, li, ki, dref, mref: (0, bi, 0)),
            pl.BlockSpec((1, 1, hdim, hdim),
                         lambda bi, li, ki, dref, mref:
                         (dref[bi, ki], li, 0, 0)),
            pl.BlockSpec((1, 1, 1, hdim),
                         lambda bi, li, ki, dref, mref:
                         (dref[bi, ki], li, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, hdim),
                               lambda bi, li, ki, dref, mref: (0, bi, 0)),
        scratch_shapes=[pltpu.VMEM((block_m, hdim), jnp.float32),
                        pltpu.VMEM((block_m, hdim), jnp.float32)],
    )
    out = pl.pallas_call(
        _score_rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, bsz, hdim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(dma, match, row_kinds[:, None], x[None], weights,
      biases[:, :, None, :])
    return out[0, :, 0]
