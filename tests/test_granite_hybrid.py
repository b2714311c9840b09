"""Granite 4.0-H (granitemoehybrid) model code against its plain reference.

A config with the published layer pattern (two periods of m m m m m a m m
m m) at small widths, float32 on seeded random weights: the program's
``transformer.forward`` / ``loss_fn`` (period scan, chunked SSD, flash
attention, slot-table expert dispatch) against ``granite_hybrid_ref``
(sequential SSD, whole score matrix, a loop over experts).  Both compute
in float32 at ``highest`` matmul precision, so what is left is summation
order: the tolerances are 1e-4, where a bfloat16 matmul anywhere would
be off by about 1e-2."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import granite_hybrid_ref as ref
from repro.models import moe as moe_mod
from repro.models import transformer

PUBLISHED = get_config("granite-4.0-h-small")
TOL = 1e-4


def small(n_layers=20, **kw):
    """The published pattern at small widths: 16 experts, top-4, this
    share holding experts 4-7; sequences of 32 over SSD chunks of 8 and
    attention chunks of 8."""
    return dataclasses.replace(
        PUBLISHED, n_layers=n_layers,
        layer_types=PUBLISHED.layer_types[:n_layers], d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=16, shared_ff=24, n_experts=16,
        top_k=4, ssm_state=8, ssm_head_dim=8, ssm_chunk=8, vocab_size=64,
        attention_multiplier=0.1, param_dtype="float32",
        act_dtype="float32", remat=False, attn_chunk_q=8, attn_chunk_kv=8,
        **{"n_experts_local": 4, "expert_offset": 4, **kw})


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.ravel(a - b))
                 / jnp.linalg.norm(jnp.ravel(b)))


@pytest.fixture(scope="module")
def case():
    cfg = small()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    return cfg, params, tokens, jnp.roll(tokens, -1, 1)


@pytest.fixture(scope="module")
def answers(case):
    """Logits, loss and gradients of the program and of the reference."""
    cfg, params, tokens, labels = case
    with jax.default_matmul_precision("highest"):
        logits, aux = jax.jit(
            lambda p, t: transformer.forward(p, cfg, t))(params, tokens)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: transformer.loss_fn(
                p, cfg, {"tokens": tokens, "labels": labels})[0]))(params)
    ref_logits = jax.jit(ref.forward, static_argnums=1)(params, cfg, tokens)
    ref_loss, ref_grads = jax.jit(ref.loss_and_grads, static_argnums=1)(
        params, cfg, tokens, labels)
    return {"program": (logits, aux, loss, grads),
            "reference": (ref_logits, ref_loss, ref_grads)}


def test_the_published_config():
    cfg = PUBLISHED
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [5, 15, 25, 35]
    assert cfg.layer_period == 10 and cfg.ssm_heads == 128
    assert cfg.resolved_head_dim == 128 and cfg.experts_held == 72
    # the model card: 32B total, 9B active
    assert 32.0e9 < cfg.n_params() < 32.5e9
    assert 8.5e9 < cfg.n_active_params() < 9.5e9


def test_n_params_counts_what_init_makes(case):
    cfg, params, _, _ = case
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.n_params()


def test_logits_match_the_reference(answers):
    logits, aux, _, _ = answers["program"]
    want = answers["reference"][0]
    assert float(aux) == 0.0
    assert float(jnp.max(jnp.abs(logits - want))
                 / jnp.max(jnp.abs(want))) < TOL


def test_loss_and_grads_match_the_reference(answers):
    _, _, loss, grads = answers["program"]
    _, ref_loss, ref_grads = answers["reference"]
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    errs = jax.tree.map(_rel, grads, ref_grads)
    assert max(jax.tree.leaves(errs)) < TOL, errs


def test_the_blocked_reference_is_the_reference(case, answers):
    cfg, params, tokens, labels = case
    _, loss, grads = answers["reference"]
    picks = ref.picks_one_per_kind(cfg)
    b_loss, b_grads = ref.loss_and_grads_blocked(params, cfg, tokens, labels,
                                                 picks, ssd_block=8)
    assert abs(float(b_loss) - float(loss)) < TOL * float(loss)
    assert set(b_grads) == set(picks)
    for name, pick in picks.items():
        assert _rel(b_grads[name], ref.grad_of(grads, cfg, pick)) < TOL, name


@pytest.mark.parametrize("capacity_factor", [1.25, 0.5])
def test_eight_expert_shares_add_up_to_the_uncut_layer(capacity_factor):
    """Each of 8 chips holds 2 of 16 experts and routes over all 16; their
    partial outputs plus the shared expert, counted once, are the whole
    layer's routed and shared output (0.5: experts drop tokens)."""
    cfg = small(n_layers=1, n_experts_local=0, expert_offset=0,
                capacity_factor=capacity_factor)
    blk = transformer.init_params(cfg, jax.random.PRNGKey(2))["layers"][0]
    blk = jax.tree.map(lambda a: a[0], blk)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole = (ref.routed_experts(blk["moe"], x, cfg)
                 + ref.shared_expert(blk["shared"], x))
        shares = [
            moe_mod.local_moe_layer(
                dict(blk["moe"], **{k: blk["moe"][k][2 * i:2 * i + 2]
                                    for k in ("w_gate", "w_up", "w_down")}),
                x, cfg.top_k, expert_offset=2 * i,
                capacity_factor=capacity_factor)
            for i in range(8)]
        shared = ref.shared_expert(blk["shared"], x)
    total = sum(shares) + shared
    assert _rel(total, whole) < TOL
    # no share is the whole: each holds a part
    assert all(_rel(s + shared, whole) > 0.1 for s in shares)


def test_the_gate_is_a_softmax_over_the_top_k_logits():
    logits = jnp.array([[1.0, 3.0, 2.0, 0.5],
                        [0.0, -1.0, 4.0, 4.5]])
    gates, experts = moe_mod.topk_softmax_gate(logits, 2)
    e = np.e
    np.testing.assert_array_equal(np.asarray(experts), [[1, 2], [3, 2]])
    np.testing.assert_allclose(
        np.asarray(gates),
        [[e / (e + 1), 1 / (e + 1)],
         [e ** 0.5 / (e ** 0.5 + 1), 1 / (e ** 0.5 + 1)]], rtol=1e-6)


def test_the_capacity_rule_fills_slots_in_token_order():
    top_e = jnp.array([[0, 1], [1, 0], [1, 2], [0, 1]])
    pos, kept = moe_mod.capacity_slots(top_e.reshape(-1), 3, capacity=2)
    np.testing.assert_array_equal(np.asarray(pos),
                                  [0, 0, 1, 1, 2, 0, 2, 3])
    np.testing.assert_array_equal(np.asarray(kept),
                                  [1, 1, 1, 1, 0, 1, 0, 0])
    # moe_layer's groups (leading axes) each count their own slots
    grouped = jnp.stack([top_e.reshape(-1), top_e.reshape(-1)[::-1]])
    gpos, _ = moe_mod.capacity_slots(grouped, 3, capacity=2)
    np.testing.assert_array_equal(np.asarray(gpos[0]), np.asarray(pos))
    np.testing.assert_array_equal(np.asarray(gpos[1]),
                                  [0, 0, 0, 1, 1, 2, 3, 2])


def test_other_families_are_untouched_by_the_attention_scale():
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 2, 8))
    from repro.models import attention
    base = attention.dense_attention(q, k, k)
    np.testing.assert_array_equal(
        np.asarray(base),
        np.asarray(attention.dense_attention(q, k, k, scale=8 ** -0.5)))
    np.testing.assert_allclose(
        np.asarray(attention.flash_attention(q, k, k, chunk_q=4, chunk_kv=4,
                                             scale=0.3)),
        np.asarray(attention.dense_attention(q, k, k, scale=0.3)),
        atol=1e-5)
