"""PR 5 hot-path tests: row-mapped fused scorer + cross-stack factor cache.

Three invariants:

  * the row-mapped scorer (``FusedMLPScorer.score_rows_ms`` and the
    kernel behind it) reproduces the per-kind jitted forwards for any
    kind mix — including single-kind degenerate batches and padded
    rows — and a cell-masked sweep with a fused scorer costs exactly
    ONE scorer dispatch (counter-asserted);
  * the module-level wave-factor cache serves ``predict_trace_batch``,
    ragged sweeps, and masked sweeps from one entry, bitwise, and can
    never serve a stale factor after a device-spec change;
  * the cache bounds (entries/bytes/env knobs) actually bound.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import HabitatPredictor, devices
from repro.core import batched
from repro.core import dataset as dataset_mod, mlp
from repro.core.batched import FusedMLPScorer
from repro.core.costmodel import OpCost
from repro.core.trace import Op, TrackedTrace
from repro.kernels import ops as kernel_ops
from repro.kernels.fused_mlp_score import bucket_blocks, bucket_rows
from test_sweep_properties import VARYING_KINDS, _make_stack

DEVS = sorted(devices.all_devices())


@pytest.fixture(scope="module")
def tiny_mlps():
    """Architecture-uniform tiny MLPs for all four kinds (seconds)."""
    cfg = mlp.MLPConfig(hidden_layers=2, hidden_size=32, epochs=2)
    out = {}
    for kind in VARYING_KINDS:
        ds = dataset_mod.build_dataset(kind, 60, device_names=["T4"])
        out[kind] = mlp.train(ds, cfg)
    return out


def _pair_rows(mlps, per_kind: int, seed: int = 0,
               kinds=None):
    """Interleaved raw feature rows + kind ids over ``kinds``."""
    rng = np.random.default_rng(seed)
    dev = devices.get("V100")
    kinds_sorted = sorted(mlps)
    feats, kind_ids = [], []
    for ki, kind in enumerate(kinds_sorted):
        if kinds is not None and kind not in kinds:
            continue
        for op in dataset_mod.sample_ops(kind, per_kind, seed=seed + ki):
            feats.append(dataset_mod.op_features(op, dev))
            kind_ids.append(ki)
    order = rng.permutation(len(feats))
    return (np.asarray(feats)[order],
            np.asarray(kind_ids, np.int32)[order])


def _check_rows_match_forwards(mlps, scorer, feats, kind_ids,
                               rtol=2e-4):
    got = scorer.score_rows_ms(feats, kind_ids)
    assert got.shape == (len(feats),)
    for ki, kind in enumerate(scorer.kinds):
        rows = np.flatnonzero(kind_ids == ki)
        if not len(rows):
            continue
        direct = mlps[kind].predict_ms(feats[rows])
        np.testing.assert_allclose(got[rows], direct, rtol=rtol,
                                   err_msg=f"{kind} ({scorer.impl})")


# ---------------------------------------------------------------------------
# row-mapped scorer vs per-kind forwards
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_score_rows_matches_per_kind_forwards(tiny_mlps, impl):
    scorer = FusedMLPScorer(tiny_mlps, block_m=8, impl=impl)
    feats, kind_ids = _pair_rows(tiny_mlps, per_kind=5)
    _check_rows_match_forwards(tiny_mlps, scorer, feats, kind_ids)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_score_rows_single_kind_degenerate(tiny_mlps, impl):
    """All rows one kind: the row map degenerates to one forward."""
    scorer = FusedMLPScorer(tiny_mlps, block_m=8, impl=impl)
    feats, _ = _pair_rows(tiny_mlps, per_kind=7, kinds=["bmm"])
    ki = scorer.kinds.index("bmm")
    kind_ids = np.full(len(feats), ki, np.int32)
    _check_rows_match_forwards(tiny_mlps, scorer, feats, kind_ids)
    # ... and agrees with the block-mapped score_ms spelling
    blocked = scorer.score_ms({"bmm": feats})["bmm"]
    np.testing.assert_allclose(scorer.score_rows_ms(feats, kind_ids),
                               blocked, rtol=2e-4)


def test_score_rows_ragged_kind_mixes(tiny_mlps):
    """Wildly unbalanced mixes (one row of one kind, many of another)."""
    scorer = FusedMLPScorer(tiny_mlps, block_m=8, impl="jnp")
    f_many, _ = _pair_rows(tiny_mlps, per_kind=11, kinds=["conv2d"])
    f_one, _ = _pair_rows(tiny_mlps, per_kind=1, kinds=["recurrent"])
    feats = np.concatenate([f_many, f_one])
    kind_ids = np.asarray([scorer.kinds.index("conv2d")] * len(f_many)
                          + [scorer.kinds.index("recurrent")], np.int32)
    _check_rows_match_forwards(tiny_mlps, scorer, feats, kind_ids)


def test_row_kernel_padding_rows_do_not_leak():
    """Kernel-level: appending garbage padding rows (kind 0, zeros) must
    not change the real rows' outputs — the score_rows_ms contract."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    K, L, H, bm = 3, 2, 16, 8
    w = jnp.asarray(rng.normal(size=(K, L, H, H)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(K, L, H)).astype(np.float32))
    x = rng.normal(size=(2 * bm, H)).astype(np.float32)
    rk = rng.integers(0, K, 2 * bm).astype(np.int32)
    base = np.asarray(kernel_ops.fused_mlp_score_rows(
        jnp.asarray(x), jnp.asarray(rk), w, b, block_m=bm, impl="jnp"))
    xp = np.concatenate([x, np.zeros((bm, H), np.float32)])
    rkp = np.concatenate([rk, np.zeros(bm, np.int32)])
    padded = np.asarray(kernel_ops.fused_mlp_score_rows(
        jnp.asarray(xp), jnp.asarray(rkp), w, b, block_m=bm, impl="jnp"))
    np.testing.assert_array_equal(padded[:2 * bm], base)


def test_row_kernel_interpret_matches_jnp():
    """The Pallas row kernel (interpret mode) vs the jnp oracle."""
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    K, L, H, bm = 4, 3, 16, 8
    w = jnp.asarray(rng.normal(size=(K, L, H, H)).astype(np.float32) * .3)
    b = jnp.asarray(rng.normal(size=(K, L, H)).astype(np.float32) * .1)
    x = jnp.asarray(rng.normal(size=(5 * bm, H)).astype(np.float32))
    rk = jnp.asarray(rng.integers(0, K, 5 * bm).astype(np.int32))
    ref = np.asarray(kernel_ops.fused_mlp_score_rows(
        x, rk, w, b, block_m=bm, impl="jnp"))
    interp = np.asarray(kernel_ops.fused_mlp_score_rows(
        x, rk, w, b, block_m=bm, impl="interpret"))
    # the kernel and the oracle sum each 16-term float32 dot in a
    # different order: a few float32 ulps of the O(1) terms, absolute,
    # which is a large relative error only where an output nears zero
    np.testing.assert_allclose(interp, ref, rtol=1e-6,
                               atol=8 * np.finfo(np.float32).eps)


def test_row_kernel_rejects_bad_shapes():
    import jax.numpy as jnp
    from repro.kernels import fused_mlp_score as fms
    x = jnp.zeros((16, 8), jnp.float32)
    w = jnp.zeros((2, 1, 8, 8), jnp.float32)
    b = jnp.zeros((2, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="row_kinds shape"):
        fms.fused_mlp_score_rows(x, jnp.zeros(4, jnp.int32), w, b,
                                 block_m=8)
    with pytest.raises(ValueError, match="not a multiple"):
        fms.fused_mlp_score_rows(x[:12], jnp.zeros(12, jnp.int32), w, b,
                                 block_m=8)


# ---------------------------------------------------------------------------
# dispatch accounting: masked sweeps cost exactly one scorer launch
# ---------------------------------------------------------------------------
def _all_kind_traces(n_traces: int, seed: int):
    """Traces whose kernel-varying ops span ALL four MLP kinds."""
    out = []
    for i in range(n_traces):
        ops = []
        for kind in VARYING_KINDS:
            ops.extend(dataset_mod.sample_ops(kind, 2, seed=seed + i))
        ops.append(Op(name="add", kind="add",
                      cost=OpCost(1e6, 6e5, 4e5)))
        t = TrackedTrace(ops=ops, origin_device="T4",
                         label=f"dispatch-{seed}-{i}")
        out.append(t.measure())
    return out


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_masked_sweep_exactly_one_fused_dispatch(tiny_mlps, impl):
    traces = _all_kind_traces(4, seed=60)
    mask = np.ones((4, len(DEVS)), bool)
    mask[:, ::2] = False                 # partial grid -> masked path
    pred = HabitatPredictor(mlps=tiny_mlps, sweep_scorer=impl)
    pred.predict_sweep(traces, DEVS, cell_mask=mask)        # warmup
    batched.SCORER_DISPATCHES.reset()
    sweep = pred.predict_sweep(traces, DEVS, cell_mask=mask)
    assert batched.SCORER_DISPATCHES.snapshot() == \
        {"fused": 1, "per_kind": 0}
    # parity vs the per-kind masked path on the computed cells
    want = HabitatPredictor(mlps=tiny_mlps).predict_sweep(
        traces, DEVS, cell_mask=mask)
    op_mask = mask[sweep.arrays.trace_ids]
    np.testing.assert_allclose(sweep.op_ms[op_mask],
                               want.op_ms[op_mask], rtol=2e-4)


def test_masked_sweep_per_kind_dispatch_count(tiny_mlps):
    """The baseline pays one forward per kind present in cold cells."""
    traces = _all_kind_traces(3, seed=70)
    mask = np.ones((3, len(DEVS)), bool)
    mask[0, 0] = False
    pred = HabitatPredictor(mlps=tiny_mlps)     # scorer "auto" -> None
    pred.predict_sweep(traces, DEVS, cell_mask=mask)
    batched.SCORER_DISPATCHES.reset()
    pred.predict_sweep(traces, DEVS, cell_mask=mask)
    counts = batched.SCORER_DISPATCHES.snapshot()
    assert counts["fused"] == 0
    assert counts["per_kind"] == len(VARYING_KINDS)


def test_full_sweep_fused_is_one_dispatch(tiny_mlps):
    traces = _all_kind_traces(3, seed=80)
    pred = HabitatPredictor(mlps=tiny_mlps, sweep_scorer="jnp")
    pred.predict_sweep(traces, DEVS)
    batched.SCORER_DISPATCHES.reset()
    pred.predict_sweep(traces, DEVS)
    assert batched.SCORER_DISPATCHES.snapshot()["fused"] == 1


@pytest.mark.parametrize("impl,masked", [("jnp", False), ("jnp", True),
                                         ("interpret", True)])
def test_stats_device_names_where_the_scorer_ran(tiny_mlps, impl, masked):
    """``/stats.device.scored_on`` comes from the scorer's outputs, not
    from where the weights were put: empty until a sweep runs it."""
    import jax
    from repro.serve.fleet import FleetPlanner
    dev = jax.devices()[-1]
    pred = HabitatPredictor(
        mlps={k: m.to_device(dev) for k, m in tiny_mlps.items()},
        sweep_scorer=impl)
    planner = FleetPlanner(predictor=pred)
    before = planner.scorer_device()
    assert (before["id"], before["chip"], before["scored_on"]) \
        == (dev.id, dev.id, [])
    mask = np.ones((2, len(DEVS)), bool)
    mask[0, 0] = not masked
    pred.predict_sweep(_all_kind_traces(2, seed=90), DEVS, cell_mask=mask)
    assert pred.built_scorer().ran_on == {dev}
    assert planner.scorer_device()["scored_on"] == [dev.id]


# ---------------------------------------------------------------------------
# cross-stack wave-factor cache
# ---------------------------------------------------------------------------
def test_predict_fleet_warm_factor_bitwise():
    trace = _make_stack(90, 1)[0]
    pred = HabitatPredictor()
    batched.WAVE_FACTOR_CACHE.clear()
    cold = pred.predict_fleet(trace, DEVS)
    hits0 = batched.WAVE_FACTOR_CACHE.stats()["hits"]
    warm = pred.predict_fleet(trace, DEVS)
    assert batched.WAVE_FACTOR_CACHE.stats()["hits"] > hits0
    np.testing.assert_array_equal(cold.op_ms, warm.op_ms)


def test_one_trace_sweep_warms_predict_factor():
    """predict() and a 1-trace sweep share one factor entry (the
    cross-stack promotion this PR exists for)."""
    trace = _make_stack(91, 1)[0]
    pred = HabitatPredictor()
    batched.WAVE_FACTOR_CACHE.clear()
    oracle = pred.predict_fleet(trace, DEVS).op_ms.copy()
    batched.WAVE_FACTOR_CACHE.clear()
    pred.predict_sweep([trace], DEVS)           # sweep mints the entry
    hits0 = batched.WAVE_FACTOR_CACHE.stats()["hits"]
    got = pred.predict_fleet(trace, DEVS)       # ... predict reuses it
    assert batched.WAVE_FACTOR_CACHE.stats()["hits"] > hits0
    np.testing.assert_array_equal(got.op_ms, oracle)


@pytest.mark.parametrize("exact,overhead", [(False, False), (True, False),
                                            (False, True)])
def test_restacked_sweep_reuses_factor_bitwise(exact, overhead):
    """A fresh restack of the same traces hits the cache (keyed by
    content fingerprints, not stack identity) and stays bitwise."""
    traces = _make_stack(92, 3)
    pred = HabitatPredictor(exact_wave=exact, model_overhead=overhead)
    batched.WAVE_FACTOR_CACHE.clear()
    cold = pred.predict_sweep(traces, DEVS).op_ms.copy()
    hits0 = batched.WAVE_FACTOR_CACHE.stats()["hits"]
    rebuilt = batched.predict_sweep(
        batched._build_stack(traces), DEVS, exact=exact,
        model_overhead=overhead, stack_cache=False)
    assert batched.WAVE_FACTOR_CACHE.stats()["hits"] > hits0
    np.testing.assert_array_equal(rebuilt.op_ms, cold)


def test_predict_minted_factor_serves_masked_overhead_sweep():
    """A masked sweep must be able to consume a predict()-minted entry —
    including the overhead arrays the grouped path indexes per row."""
    trace = _make_stack(93, 1)[0]
    pred = HabitatPredictor(model_overhead=True)
    batched.WAVE_FACTOR_CACHE.clear()
    full = pred.predict_fleet(trace, DEVS)      # mints ((fp,), ...) entry
    mask = np.ones((1, len(DEVS)), bool)
    mask[0, :4] = False
    hits0 = batched.WAVE_FACTOR_CACHE.stats()["hits"]
    masked = pred.predict_sweep([trace], DEVS, cell_mask=mask)
    assert batched.WAVE_FACTOR_CACHE.stats()["hits"] > hits0
    np.testing.assert_array_equal(masked.op_ms[:, 4:], full.op_ms[:, 4:])
    assert np.isnan(masked.op_ms[:, :4]).all()


def test_factor_cache_kill_switch_changes_nothing():
    """``factor_cache=False`` (the benchmark baseline) must be bitwise
    the cached spelling on every path, and must not touch the cache."""
    traces = _make_stack(95, 3)
    on = HabitatPredictor()
    off = HabitatPredictor(factor_cache=False)
    batched.WAVE_FACTOR_CACHE.clear()
    np.testing.assert_array_equal(
        on.predict_fleet(traces[0], DEVS).op_ms,
        off.predict_fleet(traces[0], DEVS).op_ms)
    np.testing.assert_array_equal(on.predict_sweep(traces, DEVS).op_ms,
                                  off.predict_sweep(traces, DEVS).op_ms)
    rng = np.random.default_rng(95)
    mask = rng.random((3, len(DEVS))) < 0.6
    mask[~mask.any(axis=1), 0] = True
    stats0 = batched.WAVE_FACTOR_CACHE.stats()
    m_on = on.predict_sweep(traces, DEVS, cell_mask=mask)
    m_off = off.predict_sweep(traces, DEVS, cell_mask=mask)
    np.testing.assert_array_equal(m_on.op_ms, m_off.op_ms)
    stats1 = batched.WAVE_FACTOR_CACHE.stats()
    assert stats1["inserts"] == stats0["inserts"]   # off path never wrote
    batched.WAVE_FACTOR_CACHE.clear()
    off.predict_fleet(traces[0], DEVS)
    off.predict_sweep(traces, DEVS)
    assert batched.WAVE_FACTOR_CACHE.stats()["inserts"] == 0


def test_factor_cache_spec_change_invalidates():
    """Same device names, different specs: the DeviceArrays-identity
    check must force a recompute, never serve the stale factor."""
    trace = _make_stack(94, 1)[0]
    base = [devices.get("T4"), devices.get("V100")]
    swapped = [base[0],
               dataclasses.replace(base[1], mem_bandwidth=5e9)]
    batched.WAVE_FACTOR_CACHE.clear()
    a = batched.predict_trace_batch(trace, base)
    b = batched.predict_trace_batch(trace, swapped)
    batched.WAVE_FACTOR_CACHE.clear()
    oracle = batched.predict_trace_batch(trace, swapped)
    np.testing.assert_array_equal(b.op_ms, oracle.op_ms)
    assert not np.array_equal(a.op_ms[:, 1], b.op_ms[:, 1])


def test_masked_peek_does_not_count_misses():
    """Cell-masked sweeps probe the factor cache but never insert on a
    miss — those probes must not inflate the operator-facing miss count."""
    trace = _make_stack(96, 1)[0]
    pred = HabitatPredictor()
    batched.WAVE_FACTOR_CACHE.clear()
    mask = np.ones((1, len(DEVS)), bool)
    mask[0, 0] = False
    pred.predict_sweep([trace], DEVS, cell_mask=mask)    # cold peek
    stats = batched.WAVE_FACTOR_CACHE.stats()
    assert stats["misses"] == 0 and stats["hits"] == 0
    pred.predict_sweep([trace], DEVS)                    # real miss+insert
    pred.predict_sweep([trace], DEVS, cell_mask=mask)    # warm peek: hit
    stats = batched.WAVE_FACTOR_CACHE.stats()
    assert stats["misses"] == 1 and stats["hits"] >= 1


def test_factor_cache_entry_and_byte_bounds():
    cache = batched._WaveFactorCache(capacity=2, max_bytes=1 << 30)
    da = devices.arrays_for(DEVS[:2])
    org = (devices.get("T4"),)
    for i in range(3):
        cache.insert(("k", i), da, org, np.ones((4, 2)), None)
    s = cache.stats()
    assert s["entries"] == 2 and s["evictions"] == 1
    assert cache.get(("k", 0), da, org) is None     # LRU victim
    assert cache.get(("k", 2), da, org) is not None

    tight = batched._WaveFactorCache(capacity=100, max_bytes=100)
    tight.insert(("a",), da, org, np.ones((4, 2)), None)    # 64 bytes
    tight.insert(("b",), da, org, np.ones((4, 2)), None)    # evicts "a"
    s = tight.stats()
    assert s["entries"] == 1 and s["bytes"] <= 100


def test_factor_cache_origin_spec_change_invalidates(monkeypatch):
    """The fingerprint names the origin device but does not hash its
    numbers — a replaced registry entry (tests do this; calibration
    could) must invalidate the factor, not serve the stale one."""
    ops = [Op(name="add", kind="add",
              cost=OpCost(1e6 * (i + 1), 6e5, 4e5)) for i in range(5)]
    trace = TrackedTrace(ops=ops, origin_device="T4",
                         label="origin-spec").measure()
    pred = HabitatPredictor()
    batched.WAVE_FACTOR_CACHE.clear()
    before = pred.predict_fleet(trace, DEVS).op_ms.copy()
    swapped = dataclasses.replace(devices.get("T4"),
                                  mem_bandwidth=5e9, clock_hz=7e8)
    monkeypatch.setitem(devices._REGISTRY, "T4", swapped)
    got = pred.predict_fleet(trace, DEVS)
    oracle = batched.predict_trace_batch(trace, DEVS, factor_cache=False)
    np.testing.assert_array_equal(got.op_ms, oracle.op_ms)
    assert not np.array_equal(got.op_ms, before)
    # ... and the ragged path validates the same way
    batched.WAVE_FACTOR_CACHE.clear()
    stale = pred.predict_sweep([trace], DEVS).op_ms.copy()
    monkeypatch.undo()
    fresh_stack = batched._build_stack([trace])     # new stack, old trace
    restored = batched.predict_sweep(fresh_stack, DEVS,
                                     stack_cache=False)
    np.testing.assert_array_equal(restored.op_ms, before)
    assert not np.array_equal(restored.op_ms, stale)


def test_cache_bounds_env_knobs(monkeypatch):
    monkeypatch.setenv("REPRO_FACTOR_CACHE_ENTRIES", "7")
    monkeypatch.setenv("REPRO_FACTOR_CACHE_BYTES", "1234")
    c = batched._WaveFactorCache()
    assert c.capacity == 7 and c.max_bytes == 1234
    monkeypatch.setenv("REPRO_STACK_CACHE_ENTRIES", "5")
    monkeypatch.setenv("REPRO_STACK_CACHE_BYTES", "4321")
    s = batched._StackCache()
    assert s.capacity == 5 and s.max_bytes == 4321
    # malformed / negative values keep the documented defaults
    monkeypatch.setenv("REPRO_FACTOR_CACHE_ENTRIES", "bogus")
    monkeypatch.setenv("REPRO_FACTOR_CACHE_BYTES", "-1")
    c = batched._WaveFactorCache()
    assert c.capacity == 64 and c.max_bytes == 128 << 20
    # kwargs beat the environment
    assert batched._WaveFactorCache(capacity=3).capacity == 3
    assert batched._StackCache(max_bytes=99).max_bytes == 99


def test_planner_surfaces_engine_cache_stats():
    from repro.serve.fleet import FleetPlanner
    stats = FleetPlanner(predictor=HabitatPredictor()).engine_cache_stats()
    assert set(stats) == {"stack_cache", "wave_factor_cache",
                          "scorer_dispatches"}
    for key in ("hits", "bytes", "capacity", "max_bytes"):
        assert key in stats["wave_factor_cache"]
        assert key in stats["stack_cache"]
    assert set(stats["scorer_dispatches"]) == {"fused", "per_kind"}


# ---------------------------------------------------------------------------
# jit bucket contracts
# ---------------------------------------------------------------------------
def test_bucket_blocks_zero_and_negative_contract():
    assert bucket_blocks(0) == 0
    with pytest.raises(ValueError, match=">= 0"):
        bucket_blocks(-1)


def test_score_ms_empty_inputs(tiny_mlps):
    """The zero-block contract's caller-side guard: degenerate queries
    answer directly instead of launching an empty kernel."""
    scorer = FusedMLPScorer(tiny_mlps, block_m=8, impl="jnp")
    assert scorer.score_ms({}) == {}
    empty = np.zeros((0, scorer.in_features))
    out = scorer.score_ms({"bmm": empty})
    assert list(out) == ["bmm"] and out["bmm"].shape == (0,)
    assert scorer.score_rows_ms(empty, np.zeros(0, np.int32)).shape == (0,)


def test_bucket_rows_contract():
    assert bucket_rows(0) == 0
    with pytest.raises(ValueError, match=">= 0"):
        bucket_rows(-3)
    assert [bucket_rows(n) for n in (1, 2, 3, 500, 512, 513, 1025)] \
        == [1, 2, 4, 512, 512, 1024, 1536]
    for n in range(1, 1200, 7):
        b = bucket_rows(n)
        assert b >= n and bucket_rows(b) == b


# ---------------------------------------------------------------------------
# hypothesis properties (dev-only dependency)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    given = None

if given is not None:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 40),
           st.sets(st.sampled_from(VARYING_KINDS), min_size=1))
    def test_property_score_rows_matches_forwards(tiny_mlps, seed, n,
                                                  kinds):
        rng = np.random.default_rng(seed)
        scorer = FusedMLPScorer(tiny_mlps, block_m=8, impl="jnp")
        pool, pool_ids = _pair_rows(tiny_mlps, per_kind=10, seed=seed,
                                    kinds=kinds)
        take = rng.integers(0, len(pool), size=min(n, len(pool)))
        _check_rows_match_forwards(tiny_mlps, scorer, pool[take],
                                   pool_ids[take])
