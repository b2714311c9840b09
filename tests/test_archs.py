"""Per-architecture smoke tests: every assigned arch instantiates a reduced
same-family config, runs one forward + one train step on CPU, asserts
output shapes and finiteness; decode paths agree with full forward."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import (decode_step, forward, init_params, loss_fn,
                          prefill)
from repro.models.config import SHAPES, smoke_config
from repro.train.optim import adamw, sgd
from repro.train.train_step import init_state, make_train_step


# Two cheap representative archs (dense, SSM) stay in the CI
# fast lane; the full sweep (~2 min of XLA compiles) runs with -m slow.
_FAST_ARCHS = ("qwen3-0.6b", "mamba2-130m")
ARCH_PARAMS = [pytest.param(a, marks=[] if a in _FAST_ARCHS
                            else pytest.mark.slow) for a in ARCHS]


def _batch_for(cfg, b=2, s=16, key=0):
    k = jax.random.PRNGKey(key)
    toks = jax.random.randint(k, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.frontend:
        batch["prefix_embeds"] = jax.random.normal(
            k, (b, cfg.frontend_prefix_len, cfg.frontend_dim), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = smoke_config(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("prefix_embeds"))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_train_step(arch):
    cfg = smoke_config(get_config(arch))
    optimizer = adamw(lr=1e-3)
    state = init_state(cfg, jax.random.PRNGKey(0), optimizer)
    step = jax.jit(make_train_step(cfg, optimizer))
    batch = _batch_for(cfg)
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1
    # params actually changed
    diff = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))),
        state.params, state2.params)
    assert max(jax.tree.leaves(diff)) > 0


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_decode_matches_forward(arch):
    cfg = smoke_config(get_config(arch))
    if cfg.n_experts:
        # capacity dropping differs between full-forward and decode; make
        # dispatch lossless so the invariant is exact
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    if cfg.frontend:
        cfg = dataclasses.replace(cfg, frontend="", frontend_prefix_len=0)
    params = init_params(cfg, jax.random.PRNGKey(1))
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                              cfg.vocab_size)
    lp, state = prefill(params, cfg, toks, 32)
    nxt = jnp.argmax(lp, -1).astype(jnp.int32)
    ld, state = decode_step(params, cfg, nxt, state)
    full, _ = forward(params, cfg, jnp.concatenate([toks, nxt], 1))
    np.testing.assert_allclose(np.asarray(ld[:, 0], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               atol=6e-3, rtol=1e-2)
    assert int(state["index"][0]) == s + 1


@pytest.mark.slow
def test_loss_decreases_qwen3_smoke():
    cfg = smoke_config(get_config("qwen3-0.6b"))
    optimizer = adamw(lr=3e-3)
    state = init_state(cfg, jax.random.PRNGKey(0), optimizer)
    step = jax.jit(make_train_step(cfg, optimizer))
    batch = _batch_for(cfg, b=4, s=32)  # overfit one batch
    losses = []
    for _ in range(20):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.8


@pytest.mark.slow
def test_gradient_accumulation_equivalence():
    cfg = smoke_config(get_config("qwen3-0.6b"))
    optimizer = adamw(lr=1e-3)
    batch = _batch_for(cfg, b=4, s=16)
    s0 = init_state(cfg, jax.random.PRNGKey(0), optimizer)
    step1 = jax.jit(make_train_step(cfg, optimizer, accum_steps=1,
                                    clip_norm=0.0))
    step2 = jax.jit(make_train_step(cfg, optimizer, accum_steps=2,
                                    clip_norm=0.0))
    a, _ = step1(s0, batch)
    b, _ = step2(s0, batch)
    for la, lb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        la, lb = np.asarray(la, np.float32), np.asarray(lb, np.float32)
        # summing two microbatch gradients rounds differently from one
        # full-batch gradient; Adam's first step divides by |g|, so a
        # parameter whose gradient is near zero moves by up to lr either
        # way.  Hold every parameter to one bfloat16 ulp of its value.
        mag = np.maximum(np.maximum(np.abs(la), np.abs(lb)),
                         np.finfo(np.float32).tiny)
        bf16_ulp = np.exp2(np.floor(np.log2(mag)) - 7)
        assert (np.abs(la - lb) <= bf16_ulp).all(), \
            float(np.max(np.abs(la - lb) / bf16_ulp))


@pytest.mark.parametrize("optimizer", [adamw(), sgd(momentum=0.9)],
                         ids=["adamw", "sgd-momentum"])
def test_train_state_is_a_fixed_point_of_the_step(optimizer):
    """One step returns a state of the same dtypes and shapes it was
    given — bfloat16 params included — so the jitted step never
    recompiles and a donated state is reused in place."""
    cfg = dataclasses.replace(smoke_config(get_config("qwen3-0.6b")),
                              param_dtype="bfloat16")
    state = jax.eval_shape(
        lambda: init_state(cfg, jax.random.PRNGKey(0), optimizer))
    batch = jax.eval_shape(lambda: _batch_for(cfg))
    new_state, _ = jax.eval_shape(make_train_step(cfg, optimizer),
                                  state, batch)
    assert jax.tree.structure(new_state) == jax.tree.structure(state)
    for old, new in zip(jax.tree.leaves(state), jax.tree.leaves(new_state)):
        assert (new.shape, new.dtype) == (old.shape, old.dtype)
    assert {x.dtype for x in jax.tree.leaves(state.params)} \
        == {jnp.dtype(jnp.bfloat16)}


def test_long_500k_eligibility_flags():
    """DESIGN.md §4: exactly gemma3 / mamba2 / zamba2 run long_500k."""
    eligible = {a for a in ARCHS if get_config(a).sub_quadratic}
    assert eligible == {"gemma3-1b", "mamba2-130m", "zamba2-2.7b"}


def test_param_counts_match_published():
    expected = {"minitron-4b": (3.8e9, 4.8e9), "gemma3-1b": (0.9e9, 1.1e9),
                "glm4-9b": (8.5e9, 10e9), "qwen3-0.6b": (0.5e9, 0.8e9),
                "dbrx-132b": (125e9, 140e9),
                "granite-moe-3b-a800m": (2.8e9, 3.9e9),
                "zamba2-2.7b": (2.2e9, 3.0e9),
                "mamba2-130m": (0.1e9, 0.22e9)}
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).n_params()
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B not in band"


def test_moe_active_params_below_total():
    cfg = get_config("dbrx-132b")
    assert cfg.n_active_params() < 0.45 * cfg.n_params()
