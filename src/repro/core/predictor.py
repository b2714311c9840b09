"""The Habitat predictor facade (paper Sec. 3.2) plus baseline predictors.

``HabitatPredictor`` combines:
  * **wave scaling** (Eq. 2, optionally Eq. 1) for kernel-alike ops, and
  * **pre-trained MLPs** for kernel-varying ops (conv2d / linear / bmm /
    recurrent).

When an MLP for a kind is unavailable, the predictor falls back to an
honest analytical roofline estimate (a Paleo-style model) — this fallback is
also exposed stand-alone as :class:`PaleoPredictor`, one of the baselines the
paper compares against, along with the peak-FLOPS-ratio heuristic of Fig. 1.
"""

from __future__ import annotations

import copy
import dataclasses
import pickle
from pathlib import Path
from typing import Dict, Optional, Sequence

import numpy as np

from repro.core import batched, dataset as dataset_mod
from repro.core import devices, integrity, mlp, wave_scaling
from repro.core.batched import FleetPrediction
from repro.core.devices import DeviceSpec
from repro.core.trace import Op, TrackedTrace

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "mlps"


def _analytical_ms(op: Op, dev: DeviceSpec) -> float:
    """Paleo-style analytical estimate: roofline with generic efficiency.

    Deliberately ignores the simulator's algorithm-selection factor and wave
    quantization — those are exactly the effects the paper says analytical
    models miss (Sec. 7, Paleo discussion)."""
    eff_c = 0.70 if op.kernel_varying else 0.50
    eff_m = 0.75 if op.kernel_varying else 0.82
    flops_t = op.cost.flops / (dev.peak_flops * eff_c)
    mem_t = op.cost.bytes_accessed / (dev.mem_bandwidth * eff_m)
    return max(flops_t, mem_t) * 1e3


class _FleetTraceMixin:
    """Shared glue: derive ``predict_trace`` from a ``predict_fleet`` grid."""

    def predict_trace(self, trace: TrackedTrace, dest: str) -> TrackedTrace:
        """Predict the trace on one destination (vectorized hot path)."""
        fleet = self.predict_fleet(trace, [dest])
        new_ops = [copy.copy(op) for op in trace.ops]
        for op, t in zip(new_ops, fleet.op_ms[:, 0]):
            op.predicted_ms = float(t)
        return TrackedTrace(ops=new_ops, origin_device=dest,
                            label=trace.label)

    def predict_sweep(self, traces: Sequence[TrackedTrace],
                      dests: Optional[Sequence[str]] = None
                      ) -> batched.SweepPrediction:
        """Generic multi-trace sweep: one ``predict_fleet`` grid per trace.

        Baseline predictors get the sweep API for free through this loop;
        ``HabitatPredictor`` overrides it with the one-pass ragged engine.
        Requires real ``TrackedTrace`` objects (not a prebuilt stack)."""
        if isinstance(traces, batched.RaggedTraceArrays):
            raise TypeError(
                f"{type(self).__name__}.predict_sweep needs TrackedTrace "
                f"objects; only HabitatPredictor accepts a prebuilt "
                f"RaggedTraceArrays")
        traces = list(traces)
        if dests is None:
            dests = sorted(devices.all_devices())
        ragged = batched.stack_traces(traces)
        fleets = [self.predict_fleet(t, dests) for t in traces]
        return batched.SweepPrediction(
            dests=list(fleets[0].dests),
            op_ms=np.concatenate([f.op_ms for f in fleets]),
            arrays=ragged)

    def sweep_config_key(self) -> tuple:
        """Cache-key identity of sweep() results.

        The generic sweep IS predict_fleet per trace, so the identities
        coincide; predictors whose sweep path can produce (tolerably)
        different numbers override this so the two kinds of cache entries
        never alias."""
        return self.config_key()


class HabitatPredictor(_FleetTraceMixin):
    """Scale a measured trace from its origin device to a destination."""

    def __init__(self, mlps: Optional[Dict[str, mlp.TrainedMLP]] = None,
                 exact_wave: bool = False, model_overhead: bool = False,
                 sweep_scorer: str = "auto", stack_cache: bool = True,
                 feature_buffers: bool = True, factor_cache: bool = True):
        self.mlps = mlps or {}
        self.exact_wave = exact_wave
        self.model_overhead = model_overhead
        #: MLP scorer for multi-trace sweeps: "auto" (fused Pallas on TPU,
        #: per-kind jitted forwards on CPU), "off", or a forced fused impl
        #: ("pallas" | "interpret" | "jnp").
        self.sweep_scorer = sweep_scorer
        #: hot-path plumbing knobs (results are identical either way):
        #: the fingerprint-keyed stack cache (skips ragged repacks), the
        #: pooled feature-grid buffers (skip per-pass reallocation), and
        #: the cross-stack wave-factor cache (skips the pow-heavy factor
        #: rebuild).  All off reproduces the allocate-and-recompute-
        #: everything engine — kept as the benchmark baseline and as
        #: kill switches.
        self.stack_cache = stack_cache
        self.feature_buffers = feature_buffers
        self.factor_cache = factor_cache
        self._scorer_cache: Dict = {}

    # -- per-op ------------------------------------------------------------
    def predict_op_ms(self, op: Op, origin: DeviceSpec,
                      dest: DeviceSpec) -> float:
        if op.kernel_varying:
            m = self.mlps.get(op.kind)
            if m is not None:
                feats = dataset_mod.op_features(op, dest)
                return float(m.predict_ms(feats)[0])
            return _analytical_ms(op, dest)
        if op.measured_ms is None:
            raise ValueError(f"op {op.name} has no origin measurement")
        return wave_scaling.scale_time(op.measured_ms, op, origin, dest,
                                       exact=self.exact_wave,
                                       model_overhead=self.model_overhead)

    def config_key(self) -> tuple:
        """Hashable identity of this predictor's configuration.

        Used by result caches (``serve/fleet.py``): two predictors with the
        same key produce the same predictions within this process."""
        return (type(self).__name__, self.exact_wave, self.model_overhead,
                self.sweep_scorer,
                tuple(sorted((k, m.uid) for k, m in self.mlps.items())))

    # -- whole fleet -------------------------------------------------------
    def predict_fleet(self, trace: TrackedTrace,
                      dests: Optional[Sequence[str]] = None
                      ) -> FleetPrediction:
        """Vectorized: predict the trace on every destination at once."""
        if dests is None:
            dests = sorted(devices.all_devices())
        return batched.predict_trace_batch(
            trace, dests, mlps=self.mlps, exact=self.exact_wave,
            model_overhead=self.model_overhead,
            feature_buffers=self.feature_buffers,
            factor_cache=self.factor_cache)

    # -- multi-trace ragged sweep ------------------------------------------
    def _fused_scorer(self, spelling):
        """Resolve (and cache) the fused scorer for a sweep call.

        Policy lives in :func:`batched._resolve_scorer` (one source of
        truth); this wrapper only memoizes the built scorer, since
        packing the (K, L, H, H) weight stack costs real array work and
        is reusable until the MLP set or the requested impl changes."""
        if isinstance(spelling, batched.FusedMLPScorer):
            return spelling
        key = (spelling, tuple(sorted((k, m.uid)
                                      for k, m in self.mlps.items())))
        if self._scorer_cache.get("key") != key:
            scorer = batched._resolve_scorer(spelling, self.mlps)
            self._scorer_cache = {"key": key, "scorer": scorer or "off"}
        return self._scorer_cache["scorer"]

    def built_scorer(self) -> Optional[batched.FusedMLPScorer]:
        """The fused scorer sweeps have built so far, or None (never
        builds one)."""
        scorer = self._scorer_cache.get("scorer")
        return scorer if isinstance(scorer, batched.FusedMLPScorer) else None

    def predict_sweep(self, traces, dests: Optional[Sequence[str]] = None,
                      scorer=None,
                      cell_mask=None) -> batched.SweepPrediction:
        """One ragged pass: every trace x every destination device.

        ``traces`` is a sequence of ``TrackedTrace`` or a prebuilt
        :class:`~repro.core.batched.RaggedTraceArrays`; ``scorer`` defaults
        to the predictor's ``sweep_scorer`` policy.  ``cell_mask`` (bool,
        (n_traces, n_dests), True = compute) requests a partial-compute
        sweep: only masked-in cells are evaluated, the rest stay NaN —
        the planner's cell-level cache fill rides on this."""
        if dests is None:
            dests = sorted(devices.all_devices())
        spelling = self.sweep_scorer if scorer is None else scorer
        return batched.predict_sweep(
            traces, dests, mlps=self.mlps, exact=self.exact_wave,
            model_overhead=self.model_overhead,
            scorer=self._fused_scorer(spelling), cell_mask=cell_mask,
            stack_cache=self.stack_cache,
            feature_buffers=self.feature_buffers,
            factor_cache=self.factor_cache)

    def sweep_config_key(self) -> tuple:
        """Cache-key identity of sweep() results.

        Without MLPs the ragged sweep reproduces ``predict_fleet``
        bitwise, so the identities coincide and sweep/predict caches
        interoperate.  With trained MLPs, sweep prices MLP rows in
        co-batched (and possibly fused-scorer) forwards whose float32
        results are only ~1e-6-close to the per-trace spelling — those
        cells get their own tag so they never alias predict()-minted
        entries under one key.  (``config_key()`` already embeds the
        ``sweep_scorer`` spelling, so two differently-configured
        predictors cannot collide either.)"""
        if not self.mlps:
            return self.config_key()
        return self.config_key() + ("sweep",)

    # -- whole trace: predict_trace comes from _FleetTraceMixin ------------
    def predict_trace_scalar(self, trace: TrackedTrace,
                             dest: str) -> TrackedTrace:
        """The original per-op Python loop (reference + benchmark baseline).

        Kept verbatim so ``benchmarks/bench_fleet.py`` can quantify the
        vectorized engine's speedup and tests can assert parity."""
        origin = devices.get(trace.origin_device)
        dest_spec = devices.get(dest)
        new_ops = [copy.copy(op) for op in trace.ops]
        # batch all MLP queries per kind (one fused inference each)
        by_kind: Dict[str, list] = {}
        for i, op in enumerate(new_ops):
            if op.kernel_varying and op.kind in self.mlps:
                by_kind.setdefault(op.kind, []).append(i)
            elif op.kernel_varying:
                op.predicted_ms = _analytical_ms(op, dest_spec)
            else:
                op.predicted_ms = wave_scaling.scale_time(
                    op.measured_ms, op, origin, dest_spec,
                    exact=self.exact_wave,
                    model_overhead=self.model_overhead)
        for kind, idxs in by_kind.items():
            feats = np.stack([dataset_mod.op_features(new_ops[i], dest_spec)
                              for i in idxs])
            preds = self.mlps[kind].predict_ms(feats)
            for i, p in zip(idxs, preds):
                new_ops[i].predicted_ms = float(p)
        return TrackedTrace(ops=new_ops, origin_device=dest,
                            label=trace.label)


class FlopsRatioPredictor(_FleetTraceMixin):
    """The naive heuristic the paper debunks in Fig. 1."""

    def config_key(self) -> tuple:
        return (type(self).__name__,)

    def predict_fleet(self, trace: TrackedTrace,
                      dests: Optional[Sequence[str]] = None
                      ) -> FleetPrediction:
        if dests is None:
            dests = sorted(devices.all_devices())
        origin = devices.get(trace.origin_device)
        da = devices.as_arrays(dests)
        arrays = trace.to_arrays()
        if np.isnan(arrays.measured_ms).any():
            bad = int(np.isnan(arrays.measured_ms).argmax())
            raise ValueError(
                f"op {trace.ops[bad].name} has no origin measurement")
        op_ms = (arrays.measured_ms[:, None]
                 * (origin.peak_flops / da.peak_flops)[None, :])
        return FleetPrediction(origin_device=trace.origin_device,
                               dests=list(da.names), op_ms=op_ms,
                               arrays=arrays, label=trace.label)


class PaleoPredictor(_FleetTraceMixin):
    """Purely analytical baseline (no runtime information used at all)."""

    def config_key(self) -> tuple:
        return (type(self).__name__,)

    def predict_fleet(self, trace: TrackedTrace,
                      dests: Optional[Sequence[str]] = None
                      ) -> FleetPrediction:
        if dests is None:
            dests = sorted(devices.all_devices())
        da = devices.as_arrays(dests)
        arrays = trace.to_arrays()
        op_ms = batched.analytical_ms_vec(arrays, da)
        return FleetPrediction(origin_device=trace.origin_device,
                               dests=list(da.names), op_ms=op_ms,
                               arrays=arrays, label=trace.label)


# ---------------------------------------------------------------------------
# Default predictor: MLPs trained once on simulator-labelled datasets and
# cached under artifacts/mlps/.  Small-but-sufficient config so first use
# stays fast on CPU; benchmarks train the full paper-scale MLPs themselves.
# ---------------------------------------------------------------------------
_DEFAULT: Optional[HabitatPredictor] = None
DEFAULT_MLP_CFG = mlp.MLPConfig(hidden_layers=3, hidden_size=256, epochs=30)
DEFAULT_N_CONFIGS = 2000


def train_mlps(kinds: Sequence[str] = ("conv2d", "linear", "bmm",
                                       "recurrent"),
               cfg: Optional[mlp.MLPConfig] = None,
               n_configs: int = DEFAULT_N_CONFIGS,
               device_names: Optional[Sequence[str]] = None,
               cache_dir: Optional[Path] = None,
               force: bool = False,
               verbose: bool = False) -> Dict[str, mlp.TrainedMLP]:
    """Train (or load cached) MLP predictors for the given op kinds.

    Artifacts live in a content-addressed store
    (:mod:`repro.core.artifacts`): the file name embeds a hash of the
    MLP config, dataset spec, and device specs, so a cached artifact can
    never be served for a semantically different training run — and
    refactors that do not change training semantics keep the cache
    warm (the CI cache key is the same hash)."""
    from repro.core import artifacts

    cfg = cfg or DEFAULT_MLP_CFG
    cache_dir = cache_dir or ARTIFACT_DIR
    out: Dict[str, mlp.TrainedMLP] = {}
    if device_names is None:
        # Default: the whole registry (paper GPUs + accelerators + host), so
        # the default predictor can target any registered device.  Paper-
        # parity benchmarks pass devices.PAPER_GPUS explicitly.
        device_names = sorted(devices.all_devices())
    for kind in kinds:
        path = artifacts.artifact_path(cache_dir, kind, cfg, n_configs,
                                       device_names)
        if path.exists() and not force:
            try:
                out[kind] = mlp.TrainedMLP.load(path)
                continue
            except (integrity.IntegrityError, pickle.UnpicklingError,
                    EOFError, KeyError) as e:
                # a corrupt artifact is a cache miss, not a crash: fall
                # through to retrain (which overwrites it re-sealed)
                print(f"MLP artifact {path} is corrupt ({e}); retraining")
                integrity.COUNTERS.bump("artifact")
        ds = dataset_mod.build_dataset(kind, n_configs,
                                       device_names=device_names)
        trained = mlp.train(ds, cfg, verbose=verbose)
        trained.save(path)
        out[kind] = trained
    return out


def default_predictor(force_retrain: bool = False) -> HabitatPredictor:
    global _DEFAULT
    if _DEFAULT is None or force_retrain:
        mlps = train_mlps(force=force_retrain)
        _DEFAULT = HabitatPredictor(mlps=mlps)
    return _DEFAULT
