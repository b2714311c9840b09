"""Unit tests for the Habitat core: cost model, wave scaling, γ, tracker,
MLP predictors and the end-to-end prediction pipeline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Device, FlopsRatioPredictor, HabitatPredictor,
                        OperationTracker, PaleoPredictor, gamma, scale_time)
from repro.core import costmodel, dataset as dataset_mod, devices, mlp
from repro.core import simulator, wave_scaling
from repro.core.trace import Op
from repro.core.costmodel import OpCost


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
def test_matmul_flops():
    cost = costmodel.fn_cost(lambda a, b: a @ b,
                             jnp.zeros((64, 128)), jnp.zeros((128, 32)))
    assert cost.flops == 2 * 64 * 128 * 32
    assert cost.bytes_read == 4 * (64 * 128 + 128 * 32)
    assert cost.bytes_written == 4 * 64 * 32


def test_scan_multiplies_body_cost():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out
    c1 = costmodel.fn_cost(f, jnp.zeros((8, 16)), jnp.zeros((16, 16)))
    single = costmodel.fn_cost(lambda x, w: jnp.tanh(x @ w),
                               jnp.zeros((8, 16)), jnp.zeros((16, 16)))
    assert c1.flops == pytest.approx(7 * single.flops)


def test_grad_adds_backward_ops():
    f = lambda w, x: jnp.sum(jnp.tanh(x @ w))
    fwd = costmodel.fn_cost(f, jnp.zeros((32, 32)), jnp.zeros((8, 32)))
    both = costmodel.fn_cost(jax.grad(f), jnp.zeros((32, 32)),
                             jnp.zeros((8, 32)))
    # grad-of(w) adds one extra matmul (x^T @ g) over the forward
    assert both.flops > 1.5 * fwd.flops


# ---------------------------------------------------------------------------
# wave scaling + gamma (Eqs. 1-3)
# ---------------------------------------------------------------------------
def _op(flops=1e9, bytes_=1e8):
    return Op(name="x", kind="add", cost=OpCost(flops, bytes_ * 0.7,
                                                bytes_ * 0.3))


def test_gamma_bounds_eq3():
    dev = devices.get("tpu-v5e")
    for f, b in [(1e3, 1e9), (1e9, 1e9), (1e12, 1e6)]:
        g = gamma(_op(f, b), dev)
        assert 0.0 <= g <= 1.0


def test_gamma_memory_bound_limit():
    dev = devices.get("tpu-v5e")
    # x -> 0: fully memory bound, gamma -> 1
    assert gamma(_op(1.0, 1e9), dev) == pytest.approx(1.0, abs=1e-3)
    # x -> inf: fully compute bound, gamma -> 0
    assert gamma(_op(1e15, 1e3), dev) < 0.01


def test_gamma_continuous_at_ridge():
    dev = devices.get("tpu-v5e")
    r = dev.ridge_point
    below = gamma(_op(r * 1e6 * 0.999, 1e6), dev)
    above = gamma(_op(r * 1e6 * 1.001, 1e6), dev)
    assert below == pytest.approx(0.5, abs=0.01)
    assert above == pytest.approx(0.5, abs=0.01)


def test_wave_scaling_identity():
    dev = devices.get("V100")
    op = _op()
    assert scale_time(3.0, op, dev, dev) == pytest.approx(3.0)
    assert scale_time(3.0, op, dev, dev, exact=True) == pytest.approx(3.0)


def test_wave_scaling_memory_bound_follows_bandwidth():
    op = _op(1.0, 1e9)  # gamma ~ 1
    o, d = devices.get("T4"), devices.get("V100")
    t = scale_time(10.0, op, o, d)
    assert t == pytest.approx(10.0 * o.mem_bandwidth / d.mem_bandwidth,
                              rel=0.01)


def test_flops_ratio_heuristic():
    o, d = devices.get("T4"), devices.get("V100")
    t = wave_scaling.flops_ratio_heuristic(10.0, o, d)
    assert t == pytest.approx(10.0 * o.peak_flops / d.peak_flops)


# ---------------------------------------------------------------------------
# tracker
# ---------------------------------------------------------------------------
def _toy_step(w, x):
    h = jnp.tanh(x @ w)
    return jnp.sum(jax.nn.softmax(h @ w.T))


def test_tracker_classifies_ops():
    tr = OperationTracker("cpu-host").track(
        _toy_step, jnp.zeros((32, 64)), jnp.zeros((8, 32)))
    kinds = [op.kind for op in tr.ops]
    assert kinds.count("linear") == 2
    assert all(op.measured_ms is not None for op in tr.ops)
    assert tr.run_time_ms > 0


def test_tracker_scan_becomes_recurrent():
    def f(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out
    tr = OperationTracker("cpu-host").track(
        f, jnp.zeros((16, 16)), jnp.zeros((4, 16)))
    assert any(op.kind == "recurrent" for op in tr.ops)


def test_tracker_wallclock_measurement():
    tr = OperationTracker("cpu-host", measure="wallclock").track(
        _toy_step, jnp.zeros((64, 64)), jnp.zeros((16, 64)))
    assert tr.run_time_ms > 0
    assert 0 < tr.coverage <= 1


def test_wallclock_refuses_a_foreign_origin():
    """Times taken on this process's device (the CPU here) must never be
    labelled as another device's."""
    with pytest.raises(ValueError, match="not the device this process"):
        OperationTracker("tpu-v5e", measure="wallclock").track(
            _toy_step, jnp.zeros((64, 64)), jnp.zeros((16, 64)))


def test_device_kind_table():
    assert devices.name_for_kind("TPU v5 lite") == "tpu-v5e"
    assert devices.local_device() == "cpu-host"
    for name in devices.DEVICE_KINDS.values():
        devices.get(name)               # every mapped name is registered
    with pytest.raises(KeyError, match="no registry device"):
        devices.name_for_kind("TPU v99")


# ---------------------------------------------------------------------------
# MLP predictors
# ---------------------------------------------------------------------------
@pytest.mark.slow  # trains a real MLP on a 1600-point dataset
def test_mlp_learns_dataset():
    ds = dataset_mod.build_dataset("linear", 800,
                                   device_names=["T4", "V100"])
    cfg = mlp.MLPConfig(hidden_layers=3, hidden_size=128, epochs=30)
    trained = mlp.train(ds, cfg)
    # must beat the scale-free trivial predictor by a wide margin
    assert trained.test_mape < 0.6
    preds = trained.predict_ms(ds.x[:8])
    assert preds.shape == (8,) and (preds > 0).all()


def test_mlp_extreme_features_stay_finite():
    """Regression: out-of-distribution features drove the network's
    log(ms) output past float64 ``exp``'s ~709.78 overflow point —
    ``ms_from_log`` emitted a RuntimeWarning and returned inf, which
    poisoned rankings and result caches.  Predictions must saturate to
    a huge-but-finite ceiling, silently."""
    import warnings

    cfg = mlp.MLPConfig(in_features=3, hidden_layers=1, hidden_size=4)
    trained = mlp.TrainedMLP(
        kind="linear", cfg=cfg,
        params=[(jnp.ones((3, 4)), jnp.zeros((4,))),
                (jnp.ones((4, 1)), jnp.zeros((1,)))],
        feature_mean=np.zeros(3), feature_std=np.ones(3))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        ms = trained.predict_ms(np.full((2, 3), 1e8))
        direct = mlp.TrainedMLP.ms_from_log(np.array([1e6, 800.0, -1e6]))
    assert np.isfinite(ms).all()
    # float32 inference rounds the ceiling up by one ulp
    assert (ms <= np.float32(np.exp(mlp.LOG_MS_MAX))).all()
    assert np.isfinite(direct).all()
    assert direct[0] == direct[1] == np.exp(mlp.LOG_MS_MAX)
    assert direct[2] == 1e-6            # the underflow floor still holds
    # in-distribution outputs are untouched by the clamp
    sane = np.array([-3.0, 0.0, 7.5])
    np.testing.assert_array_equal(mlp.TrainedMLP.ms_from_log(sane),
                                  np.exp(sane))


def test_mlp_save_load_roundtrip(tmp_path, tiny_mlp_cfg, tiny_n_configs):
    ds = dataset_mod.build_dataset("bmm", tiny_n_configs,
                                   device_names=["T4"])
    trained = mlp.train(ds, tiny_mlp_cfg)
    p = tmp_path / "m.pkl"
    trained.save(p)
    loaded = mlp.TrainedMLP.load(p)
    x = ds.x[:4]
    np.testing.assert_allclose(trained.predict_ms(x), loaded.predict_ms(x),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# end-to-end prediction pipeline
# ---------------------------------------------------------------------------
def test_predict_trace_runs_and_orders_devices():
    w = jnp.zeros((256, 512))
    x = jnp.zeros((64, 256))
    tr = OperationTracker("T4").track(_toy_step, w, x)
    pred = HabitatPredictor()  # analytical fallback for kernel-varying
    t_v100 = pred.predict_trace(tr, "V100").run_time_ms
    t_p4000 = pred.predict_trace(tr, "P4000").run_time_ms
    gt_v100 = simulator.trace_time_ms(tr, devices.get("V100"))
    gt_p4000 = simulator.trace_time_ms(tr, devices.get("P4000"))
    # ordering is preserved (the paper's key claim for case studies)
    assert (t_v100 < t_p4000) == (gt_v100 < gt_p4000)


@pytest.mark.slow  # trains the 4 default MLPs when artifacts/ is cold
def test_habitat_beats_flops_heuristic():
    """Fig. 1's claim: the peak-FLOPS heuristic is much worse.

    Uses the default predictor (trained MLPs, cached under artifacts/)."""
    from repro.core import default_predictor
    w = jnp.zeros((512, 512))
    x = jnp.zeros((128, 512))
    tr = OperationTracker("T4").track(_toy_step, w, x)
    habitat = default_predictor()
    flopsr = FlopsRatioPredictor()
    errs_h, errs_f = [], []
    for dest in ["V100", "P100", "RTX2080Ti", "tpu-v5e", "P4000"]:
        gt = simulator.trace_time_ms(tr, devices.get(dest))
        errs_h.append(abs(habitat.predict_trace(tr, dest).run_time_ms - gt)
                      / gt)
        errs_f.append(abs(flopsr.predict_trace(tr, dest).run_time_ms - gt)
                      / gt)
    assert np.mean(errs_h) < np.mean(errs_f)


def test_trace_breakdown_and_cost():
    from repro.core import throughput, cost_normalized_throughput
    w = jnp.zeros((128, 128))
    x = jnp.zeros((32, 128))
    tr = OperationTracker("T4").track(_toy_step, w, x)
    bd = tr.breakdown()
    assert "linear" in bd
    assert throughput(32, 10.0) == pytest.approx(3200.0)
    assert cost_normalized_throughput(32, 10.0, 1.0) == pytest.approx(
        3200.0 * 3600.0)


def test_distributed_prediction():
    from repro.core.distributed import MeshPlan, predict_step
    w = jnp.zeros((256, 256))
    x = jnp.zeros((64, 256))
    tr = OperationTracker("tpu-v4").track(_toy_step, w, x)
    plan = MeshPlan(data=16, model=16, grad_bytes=1e9,
                    weight_gather_bytes=5e8, tp_activation_bytes=1e8)
    out = predict_step(tr, "tpu-v5e", plan, predictor=HabitatPredictor())
    assert out.step_ms >= out.compute_ms
    assert out.collective_ms > 0
    plan2 = MeshPlan(data=16, model=16, pod=2, grad_bytes=1e9)
    out2 = predict_step(tr, "tpu-v5e", plan2, predictor=HabitatPredictor())
    assert "pod_all_reduce" in out2.per_collective
