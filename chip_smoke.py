"""Smoke run of the main path on a TPU: trace -> predict -> serve.

    python chip_smoke.py               # one chip: phases 1-6
    python chip_smoke.py --four-chips  # four chips: router over 4 replicas

One process drives everything through the entry points a user calls, with
qwen3-0.6b at its published widths (28 layers, d_model 1024, 16 heads with
8 KV heads, d_ff 3072, vocab 151936) and random weights from seed 0:

1. device   - JAX must run on a TPU; its ``device_kind`` names the origin
              device through ``core.devices.DEVICE_KINDS``.
2. mlps     - train the four per-kind MLPs into a directory of this run's
              own (never a pickle that happens to be on disk).
3. trace    - ``OperationTracker(measure="wallclock")`` on the training
              step: every op that can be rebuilt is timed on the chip.
4. train    - 5 steps of ``Trainer``: finite losses, one compilation.
5. predict  - ``FleetPlanner.rank`` and ``.sweep`` over decode traces of
              ``ServingEngine`` at batches 1, 2, 4; the fused Pallas scorer
              must run and agree with a NumPy float64 forward to 1e-4.
6. serve    - 8 ``/rank`` and 2 ``/sweep`` requests to a ``PredictionServer``
              in this process must answer 200 and equal phase 5's answers.

``--four-chips`` runs only the replicated path: ``serve/router.py`` in front
of four prediction servers in this process, each scoring on its own chip;
the routed answers, and each replica's own answers to the sweeps, must
equal one replica's answers to the same requests, and each replica's
``/stats`` must show its scorer's outputs coming from its own chip.

Any failed check exits non-zero.  Nothing falls back: without a TPU the run
stops at phase 1.  The last line of stdout is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import runtime  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import batched, devices, mlp  # noqa: E402
from repro.core.predictor import HabitatPredictor, train_mlps  # noqa: E402
from repro.core.trace import OperationTracker  # noqa: E402
from repro.launch.serve import decode_traces  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serve.fleet import FleetPlanner, format_fleet, format_sweep  # noqa: E402
from repro.serve.http import PredictionClient, PredictionServer  # noqa: E402
from repro.serve.router import FingerprintRouter, RouterServer  # noqa: E402
from repro.serve.service import PredictionService  # noqa: E402
from repro.train.data import SyntheticTokens  # noqa: E402
from repro.train.optim import adamw  # noqa: E402
from repro.train.train_step import init_state, make_train_step  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

ARCH = "qwen3-0.6b"
BATCH, SEQ = 4, 512             # fits 16 GB with room (memory_analysis)
DECODE_BATCHES = (1, 2, 4)
DECODE_MAX_SEQ = 1024
TRAIN_STEPS = 5
#: relative error of an MLP-predicted time, chip vs float64 reference
SCORER_RTOL = 1e-4


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke FAILED: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# -- phases -----------------------------------------------------------------
def phase_device():
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"JAX runs on {dev.platform!r} ({dev.device_kind!r}), not a TPU")
    origin = devices.name_for_kind(dev.device_kind)
    say("1 device", f"{dev.platform} {dev.device_kind!r} x "
        f"{len(jax.devices())} -> origin {origin}")
    return dev, origin


def phase_mlps(cache_dir: Path):
    t0 = time.perf_counter()
    mlps = train_mlps(cache_dir=cache_dir)
    dt = time.perf_counter() - t0
    mapes = ", ".join(f"{k} {m.test_mape:.3f}" for k, m in sorted(mlps.items()))
    say("2 mlps", f"trained {len(mlps)} MLPs in {dt:.1f} s "
        f"(test MAPE: {mapes})")
    check(len(mlps) == 4, f"expected 4 per-kind MLPs, got {sorted(mlps)}")
    return mlps


def step_trace(cfg, batch: int, seq: int, tracker):
    """The training step of ``cfg`` tracked on abstract state: tracking
    needs shapes only, so no parameters are allocated."""
    optimizer = adamw()
    state = jax.eval_shape(
        lambda: init_state(cfg, jax.random.PRNGKey(0), optimizer))
    data = SyntheticTokens(cfg, batch, seq).batch_at(0)
    return tracker.track(make_train_step(cfg, optimizer), state, data,
                         label=f"{cfg.name}-train-b{batch}-s{seq}")


def phase_trace(cfg, batch: int, seq: int, origin: str):
    t0 = time.perf_counter()
    trace = step_trace(cfg, batch, seq,
                       OperationTracker(origin_device=origin,
                                        measure="wallclock"))
    dt = time.perf_counter() - t0
    per_op_ms = trace.run_time_ms
    say("3 trace", f"{trace.label}: {len(trace.ops)} ops measured on "
        f"{trace.origin_device} by wallclock in {dt:.1f} s; coverage "
        f"{trace.coverage:.4f}; per-op sum {per_op_ms:.3f} ms")
    check(trace.origin_device == origin, "trace origin is not this device")
    check(trace.coverage > 0, "no op was measured on the device")
    check(np.isfinite(per_op_ms) and per_op_ms > 0,
          f"per-op sum {per_op_ms!r}")
    return trace


def phase_train(cfg, batch: int, seq: int, per_op_ms: float) -> None:
    trainer = Trainer(cfg, batch, seq,
                      TrainerConfig(checkpoint_dir=None, log_every=1),
                      optimizer=adamw())
    trainer.run(TRAIN_STEPS, log=lambda m: say("4 train", m))
    compiles = trainer.train_step._cache_size()
    steady_ms = float(np.median(trainer.step_times[1:])) * 1e3
    say("4 train", f"{TRAIN_STEPS} steps, first {trainer.step_times[0]:.2f} s "
        f"(compiles), steady median {steady_ms:.3f} ms; compiled "
        f"programs: {compiles}")
    say("4 train", f"measured steady step {steady_ms:.3f} ms vs traced "
        f"per-op sum {per_op_ms:.3f} ms (information only)")
    check(len(trainer.losses) == TRAIN_STEPS
          and all(np.isfinite(trainer.losses)),
          f"losses {trainer.losses}")
    check(compiles == 1, f"the step compiled {compiles} programs")


def _forward_f64(scorer, kind: str, feats: np.ndarray) -> np.ndarray:
    """Plain NumPy float64 forward of ``kind``'s packed weights in
    ``scorer``: the reference the chip's scorer kernels are held to."""
    m = scorer.mlps[kind]
    k = scorer.kinds.index(kind)
    w = np.asarray(scorer.weights[k], np.float64)       # (L, H, H)
    b = np.asarray(scorer.biases[k], np.float64)        # (L, H)
    x = (np.asarray(feats, np.float64) - m.feature_mean) / m.feature_std
    h = np.zeros((x.shape[0], w.shape[1]))
    h[:, :x.shape[1]] = x
    for li in range(w.shape[0]):
        h = h @ w[li] + b[li]
        if li < w.shape[0] - 1:
            h = np.maximum(h, 0.0)
    return mlp.TrainedMLP.ms_from_log(h[:, 0])


def _rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(got - ref) / ref))


def scorer_errors(predictor, traces, fleet, scorer_impl: str):
    """(block-kernel error, row-kernel error): the MLP-priced cells of a
    sweep, and the same feature rows in a mixed kind order through the
    row kernel, against the float64 forward."""
    scorer = batched.FusedMLPScorer(predictor.mlps, impl=scorer_impl)
    sp = predictor.predict_sweep(traces, fleet)
    da = devices.as_arrays(fleet)
    arrays = sp.arrays
    block_err = 0.0
    feats, kinds, refs = [], [], []
    for kind in scorer.kinds:
        if kind not in arrays.kinds:
            continue
        idx = np.flatnonzero(arrays.kernel_varying & (
            arrays.kind_ids == arrays.kinds.index(kind)))
        if not len(idx):
            continue
        f = batched.mlp_features_grid(arrays, idx, da)
        ref = _forward_f64(scorer, kind, f)
        block_err = max(block_err,
                        _rel_err(sp.op_ms[idx].reshape(-1), ref))
        feats.append(f)
        kinds.append(np.full(len(f), scorer.kinds.index(kind), np.int32))
        refs.append(ref)
    order = np.random.default_rng(0).permutation(sum(map(len, feats)))
    rows = scorer.score_rows_ms(np.concatenate(feats)[order],
                                np.concatenate(kinds)[order])
    return block_err, _rel_err(rows, np.concatenate(refs)[order])


def mlp_part(predictor, trace, fleet):
    """Per-device ms of ``trace``'s MLP-priced ops: the only part of an
    answer that may differ between scorer paths."""
    arrays = trace.to_arrays()
    has_mlp = np.asarray([k in predictor.mlps for k in arrays.kinds])
    rows = arrays.kernel_varying & has_mlp[arrays.kind_ids]
    op_ms = predictor.predict_fleet(trace, fleet).op_ms
    part = (op_ms[rows] * arrays.multiplicity[rows, None]).sum(axis=0)
    return dict(zip(fleet, part.tolist()))


def requests_for(train_trace, batch: int, decode):
    """8 ``/rank`` requests (4 traces x 2 orderings) and 2 ``/sweep``
    requests, the second sharing a trace with the first."""
    ranked = [(train_trace, batch)] + list(zip(decode, DECODE_BATCHES))
    ranks = [(t, b, by) for t, b in ranked for by in ("throughput", "cost")]
    sweeps = [list(decode), [train_trace, decode[0]]]
    return ranks, sweeps


def answers(planner, ranks, sweeps):
    """In-process answers: {device: ms} per rank request, then per trace
    of each sweep request."""
    out = [{c.device: c.iter_ms for c in planner.rank(t, b, by=by)}
           for t, b, by in ranks]
    for traces in sweeps:
        out.extend(planner.sweep(traces))
    return out


def served_answers(client, ranks, sweeps):
    out = [{r["device"]: r["iter_ms"] for r in client.rank(t, b, by=by)}
           for t, b, by in ranks]
    for traces in sweeps:
        out.extend(client.sweep(traces))
    return out


def compare(got, want, parts, where: str) -> float:
    """Each cell may differ from the expected answer only through its
    MLP-priced ops, by at most ``SCORER_RTOL`` of their time: a cell with
    no MLP-priced op must match bitwise.  Returns the largest deviation
    as a fraction of the MLP part."""
    worst = 0.0
    for i, (g, w, part) in enumerate(zip(got, want, parts)):
        check(sorted(g) == sorted(w), f"{where}: answer {i} devices differ")
        for dev, ms in w.items():
            dev_ms = abs(g[dev] - ms)
            if part[dev] == 0.0:
                check(g[dev] == ms, f"{where}: answer {i} {dev}: {g[dev]!r} "
                      f"!= {ms!r} with no MLP-priced op")
                continue
            worst = max(worst, dev_ms / part[dev])
            check(dev_ms <= SCORER_RTOL * part[dev],
                  f"{where}: answer {i} {dev}: {g[dev]!r} vs {ms!r} "
                  f"(MLP part {part[dev]!r})")
    return worst


def parts_for(predictor, fleet, ranks, sweeps):
    cache = {}

    def part(t):
        if t.fingerprint() not in cache:
            cache[t.fingerprint()] = mlp_part(predictor, t, fleet)
        return cache[t.fingerprint()]
    return ([part(t) for t, _, _ in ranks]
            + [part(t) for traces in sweeps for t in traces])


def phase_predict(cfg, mlps, train_trace, batch: int, origin: str,
                  scorer: str = "auto"):
    params = init_params(cfg, jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    decode = decode_traces(cfg, params, DECODE_BATCHES, DECODE_MAX_SEQ,
                           OperationTracker(origin_device=origin,
                                            measure="wallclock"), cfg.name)
    dt = time.perf_counter() - t0
    del params
    say("5 predict", f"decode traces {[len(t.ops) for t in decode]} ops "
        f"(coverage {[round(t.coverage, 4) for t in decode]}) in {dt:.1f} s")

    predictor = HabitatPredictor(mlps=mlps, sweep_scorer=scorer)
    planner = FleetPlanner(predictor=predictor)
    fleet = planner.fleet
    before = batched.SCORER_DISPATCHES.snapshot()
    t0 = time.perf_counter()
    ranking = planner.rank(train_trace, batch_size=batch)
    t_rank = time.perf_counter() - t0
    t0 = time.perf_counter()
    grid = planner.sweep(decode)
    t_sweep = time.perf_counter() - t0
    after = batched.SCORER_DISPATCHES.snapshot()
    say("5 predict", f"rank of {train_trace.label} over {len(fleet)} "
        f"devices in {t_rank * 1e3:.1f} ms:\n{format_fleet(ranking[:5])}")
    say("5 predict", f"sweep of {len(decode)} decode traces in "
        f"{t_sweep * 1e3:.1f} ms:\n"
        f"{format_sweep([t.label for t in decode], grid)}")
    fused = after["fused"] - before["fused"]
    say("5 predict", f"scorer dispatches: fused +{fused}, per-kind "
        f"+{after['per_kind'] - before['per_kind']}")
    check(fused > 0, "the fused Pallas scorer never ran")
    block_err, row_err = scorer_errors(predictor, decode, fleet, scorer)
    say("5 predict", f"scorer vs NumPy float64 forward: block kernel "
        f"{block_err:.3e}, row kernel {row_err:.3e} relative (limit "
        f"{SCORER_RTOL:g})")
    check(block_err <= SCORER_RTOL and row_err <= SCORER_RTOL,
          "scorer error over the limit")
    ranks, sweeps = requests_for(train_trace, batch, decode)
    expected = answers(planner, ranks, sweeps)
    parts = parts_for(predictor, fleet, ranks, sweeps)
    return ranks, sweeps, expected, parts


def start(server):
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return thread


def stop(server, thread) -> None:
    server.shutdown()
    thread.join(timeout=10)
    check(not thread.is_alive(), "a server thread did not stop")


def phase_serve(mlps, ranks, sweeps, expected, parts,
                scorer: str = "auto") -> None:
    service = PredictionService(
        predictor=HabitatPredictor(mlps=mlps, sweep_scorer=scorer))
    server = PredictionServer(service, host="127.0.0.1", port=0)
    thread = start(server)
    client = PredictionClient(server.url, timeout=600.0)
    t0 = time.perf_counter()
    got = served_answers(client, ranks, sweeps)   # non-200 raises
    dt = time.perf_counter() - t0
    n = len(ranks) + len(sweeps)
    worst = compare(got, expected, parts, "served vs in-process")
    stats = client.stats()
    stop(server, thread)
    say("6 serve", f"{n} of {n} answers 200 in {dt:.2f} s, equal to the "
        f"in-process answers (largest deviation {worst:.3e} of a cell's "
        f"MLP-priced time)")
    say("6 serve", f"/stats: engine_passes {stats['engine_passes']}, "
        f"scorer dispatches {stats['engine_caches']['scorer_dispatches']}, "
        f"device {stats['device']}")


def phase_replicas(cfg, batch: int, seq: int, mlps, devs, origin: str,
                   scorer: str = "auto") -> None:
    """The router in front of one prediction server per device in
    ``devs``, all in this process, against replica 0 alone."""
    tracker = OperationTracker(origin_device=origin)    # simulated times
    train_trace = step_trace(cfg, batch, seq, tracker)
    params = init_params(cfg, jax.random.PRNGKey(0))
    decode = decode_traces(cfg, params, DECODE_BATCHES, DECODE_MAX_SEQ,
                           tracker, cfg.name)
    del params
    ranks, sweeps = requests_for(train_trace, batch, decode)

    replicas = []
    for dev in devs:
        on_dev = {k: m.to_device(dev) for k, m in mlps.items()}
        server = PredictionServer(PredictionService(
            predictor=HabitatPredictor(mlps=on_dev, sweep_scorer=scorer)),
            port=0)
        replicas.append((server, start(server)))
    router = RouterServer(FingerprintRouter([s.url for s, _ in replicas]),
                          port=0)
    router_thread = start(router)
    t0 = time.perf_counter()
    routed = served_answers(PredictionClient(router.url, timeout=600.0),
                            ranks, sweeps)
    dt = time.perf_counter() - t0
    one = served_answers(PredictionClient(replicas[0][0].url, timeout=600.0),
                         ranks, sweeps)
    parts = parts_for(HabitatPredictor(mlps=mlps, sweep_scorer=scorer),
                      sorted(devices.all_devices()), ranks, sweeps)
    worst = compare(routed, one, parts, "router vs one replica")
    # the ring need not send a sweep to every replica: ask each directly,
    # so every chip runs the fused scorer and answers like replica 0
    for server, _ in replicas[1:]:
        direct = served_answers(PredictionClient(server.url, timeout=600.0),
                                [], sweeps)
        worst = max(worst, compare(direct, one[len(ranks):],
                                   parts[len(ranks):],
                                   f"{server.url} vs replica 0"))
    stats = [PredictionClient(s.url).stats() for s, _ in replicas]
    placed = [st["device"]["chip"] for st in stats]
    scored = [st["device"]["scored_on"] for st in stats]
    served = [st["requests"] for st in stats]
    stop(router, router_thread)
    for server, thread in replicas:
        stop(server, thread)
    n = len(ranks) + len(sweeps)
    say("4 chips", f"{n} requests through the router in {dt:.2f} s, and "
        f"the {len(sweeps)} sweeps sent to each other replica directly, "
        f"equal replica 0's answers (largest deviation {worst:.3e} of a "
        f"cell's MLP-priced time)")
    say("4 chips", f"replica weights on chips {placed}; scorer outputs "
        f"from chips {scored}; requests per replica {served}")
    # each replica's scorer must have run, on its own chip alone
    check(all(s == [c] for s, c in zip(scored, placed))
          and len(set(placed)) == len(devs),
          f"replicas did not score on distinct chips of their own: "
          f"weights {placed}, outputs {scored}")


def four_chips(mlp_dir: Path) -> None:
    devs = jax.devices()
    check(devs[0].platform == "tpu" and len(devs) >= 4,
          f"--four-chips needs 4 TPU chips, JAX sees {len(devs)} "
          f"{devs[0].platform} device(s)")
    origin = devices.name_for_kind(devs[0].device_kind)
    say("4 chips", f"{len(devs)} x {devs[0].device_kind!r} -> origin {origin}")
    phase_replicas(get_config(ARCH), BATCH, SEQ, phase_mlps(mlp_dir),
                   devs[:4], origin)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the router over four one-chip replicas")
    args = ap.parse_args(argv)
    runtime.use_compile_cache()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        if args.four_chips:
            four_chips(Path(tmp))
        else:
            _, origin = phase_device()
            mlps = phase_mlps(Path(tmp))
            cfg = get_config(ARCH)
            trace = phase_trace(cfg, BATCH, SEQ, origin)
            phase_train(cfg, BATCH, SEQ, trace.run_time_ms)
            ranks, sweeps, expected, parts = phase_predict(
                cfg, mlps, trace, BATCH, origin)
            phase_serve(mlps, ranks, sweeps, expected, parts)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
