"""Pure-jnp oracle for the fused multi-kind MLP scorer."""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: float32 products at full precision, as in the Pallas kernels: a TPU's
#: default lowers them to one bfloat16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def fused_mlp_score_ref(x: jnp.ndarray, block_kinds: jnp.ndarray,
                        weights: jnp.ndarray,
                        biases: jnp.ndarray) -> jnp.ndarray:
    """x (B, H); block_kinds (nb,); weights (K, L, H, H); biases (K, L, H)
    -> (B,).  B must equal nb * block_m for an integer block_m."""
    bsz, hdim = x.shape
    nb = block_kinds.shape[0]
    bm = bsz // nb
    nl = weights.shape[1]
    h = x.reshape(nb, bm, hdim).astype(jnp.float32)
    w = weights[block_kinds].astype(jnp.float32)      # (nb, L, H, H)
    b = biases[block_kinds].astype(jnp.float32)       # (nb, L, H)
    for li in range(nl):
        z = (jnp.einsum("nbh,nhk->nbk", h, w[:, li], precision=_HIGHEST)
             + b[:, li, None, :])
        h = z if li == nl - 1 else jax.nn.relu(z)
    return h.reshape(bsz, hdim)[:, 0]


def fused_mlp_score_rows_ref(x: jnp.ndarray, row_kinds: jnp.ndarray,
                             weights: jnp.ndarray,
                             biases: jnp.ndarray) -> jnp.ndarray:
    """x (B, H); row_kinds (B,) int32; weights (K, L, H, H);
    biases (K, L, H) -> (B,).

    Computes every kind's layer output and gathers each row's own —
    selection, not approximation (a row's result is exactly its kind's
    forward).  Spelled as ONE (B, H) x (H, K*H) GEMM per layer plus a
    ``take_along_axis`` row gather: gathering per-row weight stacks
    (``weights[row_kinds]`` — (B, L, H, H)) is ruinous at fleet batch
    sizes, and the masked one-hot sum costs ~4x this spelling on CPU
    XLA; all three produce identical bits (each row touches exactly one
    kind's product)."""
    nk, nl = weights.shape[0], weights.shape[1]
    hdim = x.shape[1]
    h = x.astype(jnp.float32)
    idx = row_kinds.astype(jnp.int32)[:, None, None]          # (B, 1, 1)
    for li in range(nl):
        wl = jnp.transpose(weights[:, li].astype(jnp.float32),
                           (1, 0, 2)).reshape(hdim, nk * hdim)
        zk = jnp.dot(h, wl, precision=_HIGHEST).reshape(-1, nk, hdim)
        z = (jnp.take_along_axis(zk, idx, axis=1)[:, 0]
             + biases[row_kinds, li].astype(jnp.float32))
        h = z if li == nl - 1 else jax.nn.relu(z)
    return h[:, 0]


def fused_mlp_score_stacked_ref(xs: jnp.ndarray, weights: jnp.ndarray,
                                biases: jnp.ndarray) -> jnp.ndarray:
    """xs (K, B, H) per-kind row stacks; weights (K, L, H, H);
    biases (K, L, H) -> (K, B).

    The CPU lowering of the row-mapped scorer: the engine groups rows by
    kind host-side (trivial on CPU, where there is no DMA schedule to
    feed) and this ONE jitted call runs every kind's gemm chain as a
    K-batched dot — no cross-kind select work at all, unlike the
    every-kind-per-row kernel spelling, and still exactly one dispatch.
    Padding rows are zeros; their outputs are garbage by contract."""
    nl = weights.shape[1]
    h = xs.astype(jnp.float32)
    for li in range(nl):
        z = (jnp.einsum("kbh,khj->kbj", h,
                        weights[:, li].astype(jnp.float32),
                        precision=_HIGHEST)
             + biases[:, li].astype(jnp.float32)[:, None, :])
        h = z if li == nl - 1 else jax.nn.relu(z)
    return h[..., 0]
