"""Mamba2 (state-space duality / SSD) blocks.

``ssd_chunked`` implements the chunked SSD algorithm of arXiv:2405.21060:
quadratic attention-like computation inside chunks, a linear recurrence on
chunk states across chunks.  ``ssd_reference`` is the naive sequential
recurrence used as the correctness oracle (and the Pallas kernel's ref).

Shapes: x (B, L, H, P)   dt (B, L, H)   A (H,)   B, C (B, L, G, N), G=1.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import init_dense, rms_norm


def ssd_reference(x, dt, a, b, c, d_skip=None, initial_state=None,
                  return_final=False):
    """Sequential SSD recurrence: S_t = S_{t-1} exp(dt_t A) + dt_t B_t x_t.

    ``initial_state`` (B, H, N, P) continues a sequence from an earlier
    call's final state (``return_final``), so that a long one can be run
    in pieces."""
    bs, l, h, p = x.shape
    n = b.shape[-1]
    g = b.shape[2]
    rep = h // g
    bh = jnp.repeat(b, rep, axis=2)  # (B, L, H, N)
    ch = jnp.repeat(c, rep, axis=2)

    def step(s, inp):
        xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        decay = jnp.exp(dtt * a)[..., None, None]        # (B,H,1,1)
        s = s * decay + (dtt[..., None] * bt)[..., :, None] * xt[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", ct, s)
        return s, y

    s0 = (jnp.zeros((bs, h, n, p), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          dt.transpose(1, 0, 2).astype(jnp.float32),
          bh.transpose(1, 0, 2, 3).astype(jnp.float32),
          ch.transpose(1, 0, 2, 3).astype(jnp.float32))
    s_final, ys = jax.lax.scan(step, s0, xs)
    y = ys.transpose(1, 0, 2, 3)
    if d_skip is not None:
        y = y + d_skip[None, None, :, None] * x.astype(jnp.float32)
    if return_final:
        return y.astype(x.dtype), s_final
    return y.astype(x.dtype)


def ssd_chunked(x, dt, a, b, c, d_skip=None, chunk: int = 256,
                return_final=False):
    """Chunked SSD (the paper's hardware-efficient dual form)."""
    bs, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    q = min(chunk, l)
    pad = (-l) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    lp = l + pad
    nc = lp // q
    xc = x.reshape(bs, nc, q, h, p).astype(jnp.float32)
    dtc = dt.reshape(bs, nc, q, h).astype(jnp.float32)
    bc = b.reshape(bs, nc, q, g, n).astype(jnp.float32)
    cc = c.reshape(bs, nc, q, g, n).astype(jnp.float32)
    rep = h // g
    bhc = jnp.repeat(bc, rep, axis=3)                   # (B,nc,Q,H,N)
    chc = jnp.repeat(cc, rep, axis=3)

    adt = dtc * a  # (B, nc, Q, H), negative
    cum = jnp.cumsum(adt, axis=2)

    # ---- intra-chunk (quadratic within chunk) ----------------------------
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Qi,Qj,H)
    ii = jnp.arange(q)
    causal = (ii[:, None] >= ii[None, :])
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    scores = jnp.einsum("bcihn,bcjhn->bcijh", chc, bhc)
    att = scores * decay * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att, xc)

    # ---- chunk states -----------------------------------------------------
    tail = jnp.exp(cum[:, :, -1:, :] - cum)              # (B,nc,Q,H)
    weighted = (tail * dtc)[..., None] * bhc             # (B,nc,Q,H,N)
    states = jnp.einsum("bcqhn,bcqhp->bchnp", weighted, xc)

    # ---- inter-chunk recurrence -------------------------------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])              # (B,nc,H)

    def chunk_step(s, inp):
        st, dec = inp                                    # (B,H,N,P), (B,H)
        s_prev = s
        s = s * dec[..., None, None] + st
        return s, s_prev

    s0 = jnp.zeros((bs, h, n, p), jnp.float32)
    s_final, s_prevs = jax.lax.scan(
        chunk_step, s0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    s_prevs = s_prevs.transpose(1, 0, 2, 3, 4)           # (B,nc,H,N,P)

    y_inter = jnp.einsum("bcqhn,bchnp->bcqhp", chc * jnp.exp(cum)[..., None],
                         s_prevs)
    y = (y_intra + y_inter).reshape(bs, lp, h, p)[:, :l]
    if d_skip is not None:
        y = y + d_skip[None, None, :, None] * x.reshape(bs, lp, h, p)[:, :l]
    y = y.astype(jnp.float32)
    if return_final:
        return y, s_final
    return y


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------
def init_mamba_block(key, cfg, dtype) -> Dict:
    d, di = cfg.d_model, cfg.d_inner
    g, n, h, k = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    conv_dim = di + 2 * g * n
    ks = jax.random.split(key, 4)
    return {
        "in_proj": init_dense(ks[0], (d, 2 * di + 2 * g * n + h), dtype),
        "conv_w": init_dense(ks[1], (k, conv_dim), dtype, scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out_proj": init_dense(ks[3], (di, d), dtype),
    }


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: jnp.ndarray = None):
    """Depthwise causal conv along seq.  xbc: (B, L, C); w: (K, C)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, xbc], axis=1)
    out = sum(xp[:, i:i + xbc.shape[1]] * w[i] for i in range(k))
    new_state = xp[:, -(k - 1):] if k > 1 else pad
    return jax.nn.silu(out + b), new_state


def _split_proj(cfg, proj):
    di, g, n, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xbc = proj[..., di:2 * di + 2 * g * n]
    dt = proj[..., 2 * di + 2 * g * n:]
    return z, xbc, dt


def mamba_block(params: Dict, x: jnp.ndarray, cfg, return_state=False):
    """Training/prefill Mamba2 block.  x: (B, L, D) -> (B, L, D)."""
    bs, l, d = x.shape
    di, g, n, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    p = cfg.ssm_head_dim
    proj = x @ params["in_proj"]
    z, xbc_raw, dt_raw = _split_proj(cfg, proj)
    xbc, conv_state = _causal_conv(xbc_raw, params["conv_w"],
                                   params["conv_b"])
    xs = xbc[..., :di].reshape(bs, l, h, p)
    bmat = xbc[..., di:di + g * n].reshape(bs, l, g, n)
    cmat = xbc[..., di + g * n:].reshape(bs, l, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    y, s_final = ssd_chunked(xs, dt, a, bmat, cmat, params["d_skip"],
                             chunk=cfg.ssm_chunk, return_final=True)
    y = y.reshape(bs, l, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = y @ params["out_proj"]
    if return_state:
        return out, {"conv": conv_state, "ssm": s_final}
    return out


def init_mamba_state(cfg, batch: int, dtype):
    di, g, n, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * g * n
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, h, n, cfg.ssm_head_dim), jnp.float32),
    }


def mamba_decode_step(params: Dict, x: jnp.ndarray, state: Dict,
                      cfg) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode.  x: (B, 1, D)."""
    bs = x.shape[0]
    di, g, n, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    p = cfg.ssm_head_dim
    proj = x @ params["in_proj"]
    z, xbc, dt_raw = _split_proj(cfg, proj)
    xbc, conv_state = _causal_conv(xbc, params["conv_w"], params["conv_b"],
                                   state["conv"])
    xs = xbc[..., :di].reshape(bs, h, p)
    bmat = xbc[..., di:di + g * n].reshape(bs, g, n)
    cmat = xbc[..., di + g * n:].reshape(bs, g, n)
    rep = h // g
    bh = jnp.repeat(bmat, rep, axis=1).astype(jnp.float32)
    ch = jnp.repeat(cmat, rep, axis=1).astype(jnp.float32)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"])[:, 0]  # (B,H)
    a = -jnp.exp(params["a_log"])
    decay = jnp.exp(dt * a)[..., None, None]
    s = state["ssm"] * decay + \
        (dt[..., None] * bh)[..., :, None] * xs.astype(jnp.float32)[..., None, :]
    y = jnp.einsum("bhn,bhnp->bhp", ch, s)
    y = y + params["d_skip"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bs, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    return y @ params["out_proj"], {"conv": conv_state, "ssm": s}
