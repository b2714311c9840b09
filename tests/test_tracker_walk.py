"""How the tracker walks nested calls and scans.

A nested ``jax.jit`` call is walked in place (``trace.jit_walked``); a
scan whose matmuls read a per-step slice of its ``xs`` (a layer stack's
weights, a block of keys) is walked as trip count x body
(``trace.scan_walked``); a scan whose matmuls read only loop-invariant
operands and the carry (an LSTM cell) stays one ``recurrent`` op
(``trace.scan_recurrent``); a call the walk cannot enter is priced as one
elementwise op (``trace.priced_default``)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.configs import get_config
from repro.core import OperationTracker
from repro.models import ssm, transformer

NAMES = ("trace.track", "trace.jit_walked", "trace.scan_walked",
         "trace.scan_recurrent", "trace.priced_default")


def _track(fn, *args):
    """(trace, change of each tracker counter)."""
    before = {n: telemetry.stats()[n]["count"] for n in NAMES}
    trace = OperationTracker("cpu-host").track(fn, *args)
    after = {n: telemetry.stats()[n]["count"] for n in NAMES}
    return trace, {n: after[n] - before[n] for n in NAMES}


def test_a_nested_jit_matmul_tracks_as_linear():
    @jax.jit
    def project(x, w):
        return x @ w

    trace, moved = _track(lambda x, w: jnp.tanh(project(x, w)).sum(),
                          jnp.zeros((8, 16)), jnp.zeros((16, 4)))
    assert [op.kind for op in trace.ops if op.name == "dot_general"] \
        == ["linear"]
    assert not any(op.name in ("jit", "pjit") for op in trace.ops)
    assert moved["trace.jit_walked"] == 1 and moved["trace.track"] == 1
    assert moved["trace.priced_default"] == 0


def test_a_layer_scan_gives_per_layer_ops_times_its_length():
    def stack(ws, x):
        def layer(h, w):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(layer, x, ws)[0].sum()

    trace, moved = _track(stack, jnp.zeros((6, 16, 16)), jnp.zeros((4, 16)))
    dots = [op for op in trace.ops if op.name == "dot_general"]
    assert [(op.kind, op.multiplicity) for op in dots] == [("linear", 6)]
    assert not any(op.kind == "recurrent" for op in trace.ops)
    assert moved["trace.scan_walked"] == 1
    assert moved["trace.scan_recurrent"] == 0


def test_the_backward_layer_scan_is_walked_too():
    def loss(ws, x):
        def layer(h, w):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(layer, x, ws)[0].sum()

    trace, moved = _track(jax.grad(loss), jnp.zeros((6, 16, 16)),
                          jnp.zeros((4, 16)))
    dots = [op for op in trace.ops if op.name == "dot_general"]
    assert len(dots) == 3 and all(op.multiplicity == 6 for op in dots)
    assert moved["trace.scan_walked"] == 2


def test_the_ssd_chunk_scan_gives_bmm_ops_over_chunks():
    b, l, h, p, n, q = 1, 64, 4, 8, 16, 16
    chunks = l // q
    trace, moved = _track(
        lambda x, dt, a, bm, cm: ssm.ssd_chunked(x, dt, a, bm, cm, chunk=q),
        jnp.zeros((b, l, h, p)), jnp.zeros((b, l, h)), jnp.zeros((h,)),
        jnp.zeros((b, l, 1, n)), jnp.zeros((b, l, 1, n)))
    bmm = [op for op in trace.ops if op.kind == "bmm"]
    # scores, intra-chunk output, chunk states, inter-chunk output: each
    # one batched matmul over (batch x chunks x heads)
    assert len(bmm) == 4
    assert all(op.params["b"] == b * chunks * h for op in bmm)
    assert not any(op.kind == "recurrent" for op in trace.ops)
    # the state recurrence across chunks: elementwise, once per chunk
    assert moved["trace.scan_walked"] == 1
    assert {op.multiplicity for op in trace.ops
            if op.multiplicity > 1} == {chunks}


def test_an_lstm_with_loop_invariant_weights_stays_one_recurrent_op():
    def lstm(w, xs, h0):
        def cell(carry, xt):
            h, c = carry
            z = jnp.concatenate([xt, h], -1) @ w
            i, f, g, o = jnp.split(z, 4, -1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h
        return jax.lax.scan(cell, (h0, h0), xs)[1].sum()

    trace, moved = _track(lstm, jnp.zeros((24, 64)), jnp.zeros((10, 4, 8)),
                          jnp.zeros((4, 16)))
    rec = [op for op in trace.ops if op.kind == "recurrent"]
    assert len(rec) == 1 and rec[0].params["seq"] == 10
    assert not any(op.name == "dot_general" for op in trace.ops)
    assert moved["trace.scan_recurrent"] == 1
    assert moved["trace.scan_walked"] == 0


@pytest.fixture(scope="module")
def granite_step():
    """A smoke granite train step (one SGD update): the published pattern
    over two periods at small widths, remat on, each op's shapes telling
    the layer it came from."""
    base = get_config("granite-4.0-h-small")
    cfg = dataclasses.replace(
        base, n_layers=20, layer_types=base.layer_types[:20], d_model=32,
        n_heads=4, n_kv_heads=2, head_dim=6, d_ff=16, shared_ff=20,
        n_experts=16, top_k=4, n_experts_local=2, expert_offset=6,
        ssm_state=8, ssm_head_dim=8, ssm_chunk=8, vocab_size=64,
        param_dtype="float32", act_dtype="float32", attn_chunk_q=8,
        attn_chunk_kv=8)
    params = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}

    def step(params, batch):
        value, grads = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, cfg, batch)[0])(params)
        return value, jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)

    return cfg, _track(step, params, batch)


def test_the_granite_step_has_every_layer_kind_in_its_ratio(granite_step):
    cfg, (trace, moved) = granite_step
    d, di = cfg.d_model, cfg.d_inner
    in_proj = 2 * di + 2 * cfg.ssm_state + cfg.ssm_heads
    q_width = cfg.n_heads * cfg.resolved_head_dim

    def forward_matmuls(k, n):
        # activations times a (k, n) weight: the layer's forward matmul
        return [op for op in trace.ops if op.kind == "linear"
                and op.in_shapes[1] == (k, n)]

    mamba, attn = forward_matmuls(d, in_proj), forward_matmuls(d, q_width)
    # forward and the remat recompute: 9 Mamba to 1 attention a period,
    # once per period
    assert len(mamba) == 9 * len(attn) > 0
    assert {op.multiplicity for op in mamba + attn} == {2}
    experts = [op for op in trace.ops if op.kind == "bmm"
               and op.params["b"] == cfg.experts_held]
    assert experts and {op.multiplicity for op in experts} == {2}
    assert not any(op.kind == "recurrent" for op in trace.ops)
    assert not any(op.name in ("jit", "pjit") for op in trace.ops)
    assert moved["trace.priced_default"] == 0
    assert moved["trace.scan_recurrent"] == 0
    assert moved["trace.track"] == 1
