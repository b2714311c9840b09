"""The server process of a serving cell: the program's own launcher.

    python benchmarks/chip/serve_child.py --run-dir DIR [--any-platform]
        [--scorer jnp] [--fault KIND] [--diag] -- <launcher arguments>

Runs ``repro.launch.serve.main()`` with the launcher arguments, after
three things the benchmark owns:

* a device check: JAX must run on a TPU (``--any-platform`` lifts it, for
  the CPU tests), and ``DIR/device.json`` records what JAX reports;
* the benchmark's MLPs from ``DIR/mlps.npz`` (drawn from the run's seed by
  ``reference.make_mlps``) become the default predictor that
  ``--fleet-mlps`` serves;
* a control thread that answers command files the load generator drops
  into ``DIR``: ``warm`` (compile the scorer's row-block buckets named in
  the file), ``profile_start`` / ``profile_stop`` (a ``jax.profiler``
  window written under ``DIR/profile``) and ``report`` (peak device
  memory and the compiles seen since start-up).  Each answer is the file
  ``DIR/<command>.done``; a command that fails ends the process.

``--scorer jnp`` serves the fused scorer's jnp lowering (the CPU tests).
``--fault <kind>`` plants a fault, for the runs that must read ``correct``
false: ``answer`` scales every scored time where the scorer produces it;
``order`` reverses each ranking where the answer is encoded; ``control``
puts the control's forward (``control.py``: three bfloat16 passes, one
precision below the configuration's) in the fused scorer's place.
``--diag`` records the spans of ``diag.py`` for ``report``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmarks.chip import reference  # noqa: E402

#: relative change the planted ``answer`` fault makes to scored times
FAULT_SCALE = 1.001


def install_mlps(path: Path, scorer: str = "auto"):
    """The benchmark's MLPs as the program's ``TrainedMLP`` objects, made
    the default predictor (what ``--fleet-mlps`` loads).  ``scorer`` is
    the predictor's sweep scorer: ``auto`` (the fused Pallas kernels on
    a TPU) everywhere but the CPU tests."""
    import jax.numpy as jnp
    from repro.core import mlp, predictor

    mlps = {}
    for kind, m in reference.load_mlps(path).items():
        cfg = mlp.MLPConfig(in_features=reference.N_FEATURES,
                            hidden_layers=reference.HIDDEN_LAYERS,
                            hidden_size=reference.HIDDEN)
        mlps[kind] = mlp.TrainedMLP(
            kind=kind, cfg=cfg,
            params=[(jnp.asarray(w), jnp.asarray(b))
                    for w, b in zip(m["w"], m["b"])],
            feature_mean=np.asarray(m["mean"], np.float64),
            feature_std=np.asarray(m["std"], np.float64))
    predictor._DEFAULT = predictor.HabitatPredictor(mlps=mlps,
                                                    sweep_scorer=scorer)
    return predictor._DEFAULT


def plant_fault(kind: str) -> None:
    from repro.core import batched

    if kind == "answer":
        score_ms, score_rows_ms = (batched.FusedMLPScorer.score_ms,
                                   batched.FusedMLPScorer.score_rows_ms)

        def bad_score_ms(self, feats_by_kind):
            return {k: v * FAULT_SCALE
                    for k, v in score_ms(self, feats_by_kind).items()}

        def bad_score_rows_ms(self, feats, kind_ids):
            return score_rows_ms(self, feats, kind_ids) * FAULT_SCALE

    elif kind == "control":
        from benchmarks.chip import control

        def bad_score_ms(self, feats_by_kind):
            out = {}
            for k, feats in feats_by_kind.items():
                m = self.mlps[k]
                x = m.normalize(feats)
                # rows padded to a power of two: few shapes to compile
                pad = max(128, 1 << (len(x) - 1).bit_length())
                xp = np.zeros((pad, x.shape[1]), np.float64)
                xp[:len(x)] = x
                weights = {"w": [np.asarray(w) for w, _ in m.params],
                           "b": [np.asarray(b) for _, b in m.params]}
                out[k] = m.ms_from_log(control.forward(weights, xp)[:len(x)])
            return out

        def bad_score_rows_ms(self, feats, kind_ids):
            out = np.empty(len(feats), np.float64)
            for ki, k in enumerate(self.kinds):
                rows = np.flatnonzero(kind_ids == ki)
                if len(rows):
                    out[rows] = bad_score_ms(self, {k: feats[rows]})[k]
            return out

    elif kind == "order":
        from repro.serve.service import PredictionService

        encode_rank = PredictionService.encode_rank

        def bad_encode_rank(cls, trace, choices):
            return encode_rank(trace, list(reversed(choices)))

        PredictionService.encode_rank = classmethod(bad_encode_rank)
        return
    else:
        raise SystemExit(f"unknown fault {kind!r}")
    batched.FusedMLPScorer.score_ms = bad_score_ms
    batched.FusedMLPScorer.score_rows_ms = bad_score_rows_ms


class Control:
    """Answers the load generator's command files (see the module doc)."""

    def __init__(self, run_dir: Path, predictor):
        self.dir = run_dir
        self.predictor = predictor
        self.compiles = []              # (wall-clock end, seconds)
        self.cache_hits = []            # wall-clock times
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_) -> None:
        # recorded around a persistent-cache read as around a compile
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.time(), secs))

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(time.time())

    def _done(self, cmd: str, payload=None) -> None:
        tmp = self.dir / f".{cmd}.done"
        tmp.write_text(json.dumps(payload if payload is not None else {}))
        os.replace(tmp, self.dir / f"{cmd}.done")

    def warm(self, spec: dict) -> dict:
        """Compile the fused scorer's kernels at each row-block count of
        ``spec["blocks"]``: the buckets the cell's traffic reaches."""
        from repro.core import batched

        scorer = self.predictor._fused_scorer(self.predictor.sweep_scorer)
        if not isinstance(scorer, batched.FusedMLPScorer):
            raise RuntimeError("the served predictor built no fused scorer")
        t0 = time.perf_counter()
        bm, kind = scorer.block_m, scorer.kinds[0]
        for nb in spec["blocks"]:
            rows = np.zeros((nb * bm, reference.N_FEATURES), np.float32)
            scorer.score_ms({kind: rows})
            scorer.score_rows_ms(rows, np.zeros(len(rows), np.int32))
        return {"seconds": time.perf_counter() - t0}

    def report(self) -> dict:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        out = {"memory_peak_bytes": stats.get("peak_bytes_in_use"),
               "compiles": self.compiles, "cache_hits": self.cache_hits}
        if "benchmarks.chip.diag" in sys.modules:
            out["diag"] = sys.modules["benchmarks.chip.diag"].snapshot()
        return out

    def loop(self) -> None:
        try:
            self._loop()
        except Exception:
            import traceback

            traceback.print_exc()
            sys.stderr.flush()
            os._exit(70)            # the load generator sees the exit

    def _loop(self) -> None:
        import jax

        while True:
            for cmd in ("warm", "profile_start", "profile_stop", "report"):
                path = self.dir / cmd
                if not path.exists():
                    continue
                spec = json.loads(path.read_text() or "{}")
                path.unlink()
                if cmd == "warm":
                    self._done(cmd, self.warm(spec))
                elif cmd == "profile_start":
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 2
                    jax.profiler.start_trace(str(self.dir / "profile"),
                                             profiler_options=opts)
                    self._done(cmd, {"t": time.time()})
                elif cmd == "profile_stop":
                    jax.profiler.stop_trace()
                    self._done(cmd, {"t": time.time()})
                else:
                    self._done(cmd, self.report())
            time.sleep(0.005)


def main() -> None:
    argv = sys.argv[1:]
    split = argv.index("--")
    own, launcher_args = argv[:split], argv[split + 1:]
    run_dir = Path(own[own.index("--run-dir") + 1])
    import jax

    dev = jax.devices()[0]
    (run_dir / "device.json").write_text(json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}))
    if dev.platform != "tpu" and "--any-platform" not in own:
        sys.exit(f"serve_child: JAX runs on {dev.platform!r}, not a TPU")
    if "--fault" in own:
        plant_fault(own[own.index("--fault") + 1])
    if "--diag" in own:
        from benchmarks.chip import diag

        diag.install_server()
    predictor = install_mlps(
        run_dir / "mlps.npz",
        own[own.index("--scorer") + 1] if "--scorer" in own else "auto")
    # every scorer bucket, however quick to compile, goes into the
    # persistent cache, so that only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    threading.Thread(target=Control(run_dir, predictor).loop,
                     daemon=True).start()
    from repro.launch import serve

    sys.argv = ["repro.launch.serve"] + launcher_args
    serve.main()


if __name__ == "__main__":
    main()
