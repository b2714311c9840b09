"""Vectorized fleet-prediction engine: traces against many devices.

The serving question Habitat answers is "from the one device you own, rank
every device you could buy" (Sec. 5.3) — at production scale that is one
trace predicted against *dozens* of destinations per request.  The per-op
Python loop in the original ``HabitatPredictor.predict_trace`` pays the
interpreter cost once per (op, device) pair; this module pays it once per
trace — and, for fleet-wide what-if sweeps (many batch sizes / model
variants x many devices), once per *stack* of traces.

The pipeline is fully array-shaped:

  * kernel-alike ops   -> ``wave_scaling.scale_times_vec`` fills the whole
                          (n_ops x n_devices) grid in one NumPy expression,
  * kernel-varying ops -> one batched MLP inference per kind covering *all*
                          destinations at once (features tiled device-major),
                          falling back to a vectorized Paleo-style roofline
                          when no MLP is available for a kind.

``FleetPrediction`` keeps the per-(op, device) grid so per-kind breakdowns
and per-device totals are both O(1) array reductions afterwards.

Multi-trace layer: :func:`stack_traces` concatenates several traces into a
:class:`RaggedTraceArrays` (one structure-of-arrays with segment offsets),
:func:`predict_sweep` fills the whole (total_ops x n_devices) grid in one
pass — segment-aware wave scaling handles per-trace origins, and when all
four op-kind MLPs share an architecture the kernel-varying rows can be
scored by ONE fused Pallas launch (:class:`FusedMLPScorer`) instead of
four jitted per-kind forwards.  Row i of the resulting
:class:`SweepPrediction` equals ``predict_trace_batch`` on trace i alone:
bitwise on the wave-scaling and analytical paths, and to float32-forward
tolerance (~1e-6) on trained-MLP rows, whose jitted batches pad to
different shapes in the two spellings.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro import telemetry
from repro.core import dataset as dataset_mod
from repro.core import devices, wave_scaling
from repro.core.devices import DeviceArrays, DeviceSpec
from repro.core.trace import TraceArrays, TrackedTrace

#: Paleo-fallback efficiencies, matching ``predictor._analytical_ms``.
_EFF_COMPUTE = (0.50, 0.70)   # (kernel-alike, kernel-varying)
_EFF_MEMORY = (0.82, 0.75)


def _env_num(name: str, default, cast):
    """A numeric knob from the environment, falling back on bad input.

    The ONE parse-or-keep-the-default policy for every env knob in the
    engine and the serve layer (cache bounds here, the split-planner
    seeds in ``serve.service``): a malformed or negative override must
    not take a worker down — the documented default applies instead."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        value = cast(raw)
    except ValueError:
        return default
    return value if value >= 0 else default


def env_int(name: str, default: int) -> int:
    return _env_num(name, default, int)


def env_float(name: str, default: float) -> float:
    return _env_num(name, default, float)


class _DispatchCounters:
    """Process-wide MLP scorer-dispatch accounting.

    ``fused`` counts one-launch scorer calls (``fused_mlp_score`` /
    ``fused_mlp_score_rows``); ``per_kind`` counts individual per-kind
    ``predict_ms`` forwards.  The dispatch-count model of the hot path
    (README "Performance") is asserted against these by the tests and
    ``benchmarks/bench_dispatch.py`` — a refactor that silently
    re-introduces a per-kind loop fails the counter gates, not just a
    timing gate."""

    def __init__(self):
        self._lock = threading.Lock()
        self.fused = 0
        self.per_kind = 0

    def bump(self, which: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, which, getattr(self, which) + n)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"fused": self.fused, "per_kind": self.per_kind}

    def reset(self) -> None:
        with self._lock:
            self.fused = 0
            self.per_kind = 0


#: dispatch accounting for every MLP scoring path (see class docstring)
SCORER_DISPATCHES = _DispatchCounters()


class _RowCounters:
    """Process-wide rows of the fused scorer's launches: ``real`` feature
    rows, and ``padded`` rows added to fill whole row blocks and the jit
    bucket (``/stats`` ``scorer_rows``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.real = 0
        self.padded = 0

    def add(self, real: int, launched: int) -> None:
        with self._lock:
            self.real += real
            self.padded += launched - real

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"real": self.real, "padded": self.padded}


#: rows the fused scorer launched, real and padding
SCORER_ROWS = _RowCounters()


class _WaveFactorCache:
    """Cross-stack LRU of t-independent wave-scaling factor grids.

    The factor grid (``wave_scaling.wave_factor_vec``) is a pure function
    of the kernel-alike op arrays and the destination fleet — it carries
    all of the pow-heavy work, while the final ``t * factor`` combine is
    a single multiply.  PR 4 cached it per ``RaggedTraceArrays``, so the
    factor died with its stack: repeat single-trace ``predict()`` traffic
    and freshly-restacked sweeps recomputed it from scratch.  This cache
    is module-level and keyed by

        (content token, fleet names, exact, overhead-model token)

    where the content token is the tuple of trace fingerprints (a single
    trace is the 1-tuple, so ``predict()`` and a 1-trace sweep SHARE the
    entry).  Every entry stores the ``DeviceArrays`` instance AND the
    origin ``DeviceSpec`` tuple it was minted against; a lookup only
    hits when the caller presents the *same* destination instance
    (``devices.as_arrays`` memoizes one instance per distinct spec
    tuple, so identity implies spec content) and value-equal origin
    specs (the fingerprint names the origin but does not hash its
    numbers, so a replaced registry entry must invalidate).  Either way
    a same-named device with different specs can never be served a
    stale factor — the stale entry is simply overwritten on recompute.

    Bounded by entry count AND bytes (env ``REPRO_FACTOR_CACHE_ENTRIES``
    / ``REPRO_FACTOR_CACHE_BYTES``, defaults 64 entries / 128 MiB);
    thread-safe (the serving layer's coalescing leaders are concurrent
    short-lived threads)."""

    def __init__(self, capacity: Optional[int] = None,
                 max_bytes: Optional[int] = None):
        self.capacity = (env_int("REPRO_FACTOR_CACHE_ENTRIES", 64)
                         if capacity is None else capacity)
        self.max_bytes = (env_int("REPRO_FACTOR_CACHE_BYTES", 128 << 20)
                          if max_bytes is None else max_bytes)
        self._data: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        self._lock = threading.Lock()
        self._total_bytes = 0
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0

    @staticmethod
    def _entry_bytes(factor: np.ndarray, overheads) -> int:
        n = factor.nbytes
        if overheads is not None:
            n += overheads[0].nbytes + overheads[1].nbytes
        return n

    def get(self, key: Tuple, da: DeviceArrays, origins: Tuple):
        """(factor, overheads) when warm for this exact ``DeviceArrays``
        instance and value-equal origin specs, else None (counted as a
        miss)."""
        return self._lookup(key, da, origins, count_miss=True)

    def peek(self, key: Tuple, da: DeviceArrays, origins: Tuple):
        """Like :meth:`get` but a cold probe is NOT counted as a miss:
        masked sweeps probe opportunistically and by design never insert
        on a miss (a partial fill must not pay the full-grid factor
        build), so counting those probes would poison the hit ratio the
        shutdown log tells operators to tune bounds by."""
        return self._lookup(key, da, origins, count_miss=False)

    def _lookup(self, key: Tuple, da: DeviceArrays, origins: Tuple,
                count_miss: bool):
        with self._lock:
            entry = self._data.get(key)
            if entry is not None and entry[0] is da and entry[1] == origins:
                self._data.move_to_end(key)
                self.hits += 1
                return entry[2], entry[3]
            if count_miss:
                self.misses += 1
            return None

    def insert(self, key: Tuple, da: DeviceArrays, origins: Tuple,
               factor: np.ndarray, overheads) -> None:
        nbytes = self._entry_bytes(factor, overheads)
        with self._lock:
            old = self._data.pop(key, None)
            if old is not None:
                self._total_bytes -= old[4]
            self._data[key] = (da, origins, factor, overheads, nbytes)
            self._total_bytes += nbytes
            self.inserts += 1
            while self._data and (len(self._data) > self.capacity
                                  or self._total_bytes > self.max_bytes):
                _, evicted = self._data.popitem(last=False)
                self._total_bytes -= evicted[4]
                self.evictions += 1

    def stats(self) -> Dict[str, int]:
        """Counter snapshot under the lock (the ``/stats`` payload)."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "inserts": self.inserts, "evictions": self.evictions,
                    "entries": len(self._data),
                    "bytes": self._total_bytes,
                    "capacity": self.capacity,
                    "max_bytes": self.max_bytes}

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self._total_bytes = 0
            self.hits = self.misses = self.inserts = self.evictions = 0

    def export_state(self) -> List[Tuple]:
        """Pickle-safe snapshot of every entry (``serve/snapshot.py``).

        The stored ``DeviceArrays`` instance is identity-validated and
        cannot survive a process boundary, so each exported entry ships
        ``(key, origins, factor, overheads)`` only — the fleet names
        ride inside the key and :meth:`import_state` re-resolves them."""
        with self._lock:
            return [(key, e[1], e[2], e[3])
                    for key, e in self._data.items()]

    def import_state(self, entries) -> int:
        """Restore :meth:`export_state` entries into this cache.

        Each entry's fleet names are re-resolved through the memoized
        ``devices.arrays_for`` — yielding the exact instance the engine
        will present on lookup — so the instance-identity staleness
        guard keeps working after restore.  Entries naming devices no
        longer in the registry are skipped (the registry moved on; a
        stale factor must stay cold).  Returns the number restored."""
        restored = 0
        for key, origins, factor, overheads in entries:
            try:
                da = devices.arrays_for(key[1])
            except KeyError:
                continue
            self.insert(key, da, origins, factor, overheads)
            restored += 1
        return restored


#: the process-wide cross-stack wave-factor cache (see class docstring)
WAVE_FACTOR_CACHE = _WaveFactorCache()


def _factor_key(content: Tuple, da: DeviceArrays, exact: bool,
                model_overhead: bool) -> Tuple:
    """The one factor-cache key spelling shared by the single-trace and
    ragged paths, so a 1-trace stack and ``predict()`` on that trace hit
    the same entry."""
    return (content, tuple(da.names), exact, model_overhead)


def _roofline_core(flops, bytes_accessed, kernel_varying, peak_flops,
                   mem_bandwidth) -> np.ndarray:
    """Paleo-style roofline on broadcast-ready arrays.

    The one roofline expression behind both the grid and flat-cell
    spellings (the same drift guard ``_gamma_core`` provides for γ):
    every output element is produced by the same IEEE operation sequence
    regardless of input shapes, so the cell-masked sweep's bitwise
    parity with the full grid cannot be broken by editing one copy."""
    eff_c = np.where(kernel_varying, _EFF_COMPUTE[1], _EFF_COMPUTE[0])
    eff_m = np.where(kernel_varying, _EFF_MEMORY[1], _EFF_MEMORY[0])
    flops_t = (flops * (1.0 / eff_c)) / peak_flops
    mem_t = (bytes_accessed * (1.0 / eff_m)) / mem_bandwidth
    return np.maximum(flops_t, mem_t) * 1e3


def analytical_ms_vec(arrays: Union[TraceArrays, "RaggedTraceArrays"],
                      dests: DeviceArrays) -> np.ndarray:
    """Vectorized Paleo-style roofline estimate, shape (n_ops, n_dev)."""
    return _roofline_core(
        arrays.flops[:, None], arrays.bytes_accessed[:, None],
        np.asarray(arrays.kernel_varying)[:, None],
        dests.peak_flops[None, :], dests.mem_bandwidth[None, :])


def analytical_ms_flat(arrays, dests: DeviceArrays,
                       dest_idx: np.ndarray) -> np.ndarray:
    """Flat-cell spelling of :func:`analytical_ms_vec`, shape (M,).

    ``arrays`` rows are already gathered per cell; ``dest_idx[k]`` selects
    cell ``k``'s device.  The roofline formula is element-wise, so each
    cell equals the corresponding full-grid element bitwise — the
    cell-masked sweep relies on that to keep cached values history-free."""
    j = np.asarray(dest_idx, np.intp)
    return _roofline_core(arrays.flops, arrays.bytes_accessed,
                          arrays.kernel_varying, dests.peak_flops[j],
                          dests.mem_bandwidth[j])


def mlp_features_grid(arrays: Union[TraceArrays, "RaggedTraceArrays"],
                      idx: np.ndarray,
                      dests: DeviceArrays) -> np.ndarray:
    """MLP query features for ops ``idx`` x all devices, device-major rows.

    Row ``i * n_dev + j`` is op ``idx[i]`` queried against device ``j`` —
    the same log1p transform as :func:`repro.core.dataset.op_features`.

    This is the allocate-per-call reference spelling (kept as the
    ``feature_buffers=False`` compat path and as the oracle the buffered
    builder is tested against); the sweep hot path uses the preallocated
    split-transform builders below, which produce bitwise-identical rows
    without re-tiling or re-transforming the full grid per pass."""
    n_idx, n_dev = len(idx), dests.n
    op_part = np.repeat(arrays.op_features[idx], n_dev, axis=0)
    dev_part = np.tile(dests.feature_matrix, (n_idx, 1))
    raw = np.concatenate([op_part, dev_part], axis=1)
    return dataset_mod.transform_features(raw)


class _FeatureBufferPool:
    """Reusable float32 row buffers for the MLP feature grids.

    ``mlp_features_grid`` used to allocate (and log1p-transform) the full
    device-major grid on every sweep; this pool checks buffers out for
    the duration of one scoring call and back in afterwards, so repeated
    passes reuse storage instead of churning the allocator.  Checkout is
    exclusive (a buffer is never visible to two callers), which keeps
    concurrent planner/service threads safe without thread-local state —
    the service's coalescing leaders are short-lived threads, so
    thread-local buffers would never be reused."""

    _MAX_FREE = 8               # buffers kept per row width
    _MAX_BYTES = 16 << 20       # never retain one buffer above 16 MiB

    def __init__(self):
        self._free: Dict[int, List[np.ndarray]] = {}
        self._lock = threading.Lock()

    def acquire(self, n_rows: int, n_cols: int) -> np.ndarray:
        with self._lock:
            free = self._free.get(n_cols, [])
            for i, buf in enumerate(free):
                if buf.shape[0] >= n_rows:
                    return free.pop(i)
        cap = 1 << max(int(n_rows) - 1, 0).bit_length()
        return np.empty((max(cap, 1), n_cols), np.float32)

    def release(self, buf: np.ndarray) -> None:
        if buf.nbytes > self._MAX_BYTES:
            return      # one-off giant grids go back to the allocator
        with self._lock:
            free = self._free.setdefault(buf.shape[1], [])
            if len(free) < self._MAX_FREE:
                free.append(buf)


_FEATURE_BUFFERS = _FeatureBufferPool()


def _features_grid_into(buf: np.ndarray, op_feats_t: np.ndarray,
                        dev_feats_t: np.ndarray) -> np.ndarray:
    """Fill ``buf`` with the device-major feature grid, zero fresh allocs.

    ``op_feats_t``/``dev_feats_t`` are the *already transformed* op and
    device feature blocks: log1p is element-wise, so transforming each
    block once and broadcasting the results into the row grid yields the
    same bits as ``mlp_features_grid``'s transform-the-tiled-grid
    spelling, at 1/n_dev (op side) and 1/n_ops (device side) of the
    transform work."""
    n_idx, n_op_f = op_feats_t.shape
    n_dev, n_dev_f = dev_feats_t.shape
    rows = buf[:n_idx * n_dev]
    grid = rows.reshape(n_idx, n_dev, n_op_f + n_dev_f)
    grid[:, :, :n_op_f] = op_feats_t[:, None, :]
    grid[:, :, n_op_f:] = dev_feats_t[None, :, :]
    return rows


def _features_pairs_into(buf: np.ndarray, op_feats_t: np.ndarray,
                         dev_feats_t: np.ndarray, rows: np.ndarray,
                         cols: np.ndarray) -> np.ndarray:
    """Feature rows for an explicit (op, device) cell list (masked sweeps).

    Row ``k`` is op ``rows[k]`` x device ``cols[k]`` — identical bits to
    the corresponding ``mlp_features_grid`` row, but only the requested
    cells are materialized."""
    n_op_f = op_feats_t.shape[1]
    out = buf[:len(rows)]
    out[:, :n_op_f] = op_feats_t[rows]
    out[:, n_op_f:] = dev_feats_t[cols]
    return out


@dataclasses.dataclass
class FleetPrediction:
    """Per-(op, device) prediction grid for one trace against a fleet."""
    origin_device: str
    dests: List[str]
    op_ms: np.ndarray            # (n_ops, n_dev) single-execution times
    arrays: TraceArrays
    label: str = "iteration"

    @property
    def total_ms(self) -> np.ndarray:
        """Predicted iteration time per destination device, shape (n_dev,).

        Reduced with ``np.add.reduceat`` (strictly sequential row
        accumulation) rather than ``.sum(axis=0)`` (pairwise): the ragged
        sweep reduces its segments the same way, so a sweep row's totals
        equal this single-trace spelling BITWISE at any op count —
        pairwise association varies with segment size and would break
        that parity for traces over a few rows."""
        weighted = self.op_ms * self.arrays.multiplicity[:, None]
        if not weighted.shape[0]:
            return np.zeros(weighted.shape[1], weighted.dtype)
        return np.add.reduceat(weighted, [0], axis=0)[0]

    def time_for(self, dest: str) -> float:
        return float(self.total_ms[self.dests.index(dest)])

    def as_dict(self) -> Dict[str, float]:
        return dict(zip(self.dests, self.total_ms.tolist()))

    def breakdown(self, dest: str) -> Dict[str, float]:
        """Per-kind time breakdown on one destination (paper Fig. 4)."""
        j = self.dests.index(dest)
        weighted = self.op_ms[:, j] * self.arrays.multiplicity
        totals = np.bincount(self.arrays.kind_ids, weights=weighted,
                             minlength=len(self.arrays.kinds))
        return {k: float(t) for k, t in zip(self.arrays.kinds, totals)}


def _mlp_kind_rows(arrays, mlps: Dict):
    """Yield (kind, row indices) for each op kind with a trained MLP and
    at least one kernel-varying row — the one filter shared by the
    per-kind, fused, and masked scoring paths."""
    for kid, kind in enumerate(arrays.kinds):
        if kind not in mlps:
            continue
        idx = np.flatnonzero(arrays.kernel_varying
                             & (arrays.kind_ids == kid))
        if len(idx):
            yield kind, idx


def _mlp_scores_per_kind(arrays, da: DeviceArrays, mlps: Dict,
                         out: np.ndarray,
                         feature_buffers: bool = True) -> None:
    """Kernel-varying MLP rows: one jitted forward per kind, covering every
    destination device in the same batch.  Shared by the single-trace and
    ragged paths: the feature rows are identical, so pure-NumPy MLPs agree
    bitwise; real jitted forwards agree to float32 tolerance (the ragged
    batch pads to a different shape).

    ``feature_buffers=True`` routes the grid build through the pooled
    split-transform spelling (same bits, no per-pass reallocation);
    ``False`` keeps the allocate-per-call :func:`mlp_features_grid`
    reference path (benchmark baseline / kill switch)."""
    dev_t = (dataset_mod.transform_features(da.feature_matrix)
             if feature_buffers else None)
    n_feat = arrays.op_features.shape[1] + da.feature_matrix.shape[1]
    for kind, idx in _mlp_kind_rows(arrays, mlps):
        SCORER_DISPATCHES.bump("per_kind")
        if feature_buffers:
            op_t = dataset_mod.transform_features(arrays.op_features[idx])
            buf = _FEATURE_BUFFERS.acquire(len(idx) * da.n, n_feat)
            try:
                feats = _features_grid_into(buf, op_t, dev_t)
                preds = mlps[kind].predict_ms(feats)
            finally:
                _FEATURE_BUFFERS.release(buf)
        else:
            preds = mlps[kind].predict_ms(mlp_features_grid(arrays, idx,
                                                            da))
        out[idx] = preds.reshape(len(idx), da.n)


def predict_trace_batch(trace: TrackedTrace,
                        dests: Union[DeviceArrays, Sequence[str],
                                     Sequence[DeviceSpec]],
                        mlps: Optional[Dict] = None,
                        exact: bool = False,
                        model_overhead: bool = False,
                        feature_buffers: bool = True,
                        factor_cache: bool = True) -> FleetPrediction:
    """Predict one trace's per-op times on every destination at once.

    ``factor_cache=False`` bypasses :data:`WAVE_FACTOR_CACHE` and runs
    the unsplit ``scale_times_vec`` inline — bitwise the same numbers,
    kept as the benchmark baseline / kill switch (the cache is
    content-keyed, so even cache-averse callers would otherwise share
    warm factors across the process)."""
    origin = devices.get(trace.origin_device)
    da = devices.as_arrays(dests)
    arrays = trace.to_arrays()
    mlps = mlps or {}
    out = np.empty((arrays.n_ops, da.n), np.float64)

    # kernel-alike: wave scaling over the whole grid, with the
    # t-independent factor served from the cross-stack cache — repeat
    # predict()/predict_fleet() traffic (and 1-trace sweeps, which share
    # the key) skip the pow-heavy wave_factor_vec and pay only the
    # t * factor combine, which is bitwise the unsplit scale_times_vec
    alike = ~arrays.kernel_varying
    if alike.any():
        t_o = arrays.measured_ms[alike]
        if np.isnan(t_o).any():
            bad = int(np.flatnonzero(alike)[np.isnan(t_o).argmax()])
            raise ValueError(
                f"op {trace.ops[bad].name} has no origin measurement")
        sub = SimpleNamespace(intensity=arrays.intensity[alike],
                              bytes_accessed=arrays.bytes_accessed[alike])
        if not factor_cache:
            out[alike] = wave_scaling.scale_times_vec(
                t_o, sub, origin, da, exact=exact,
                model_overhead=model_overhead)
        else:
            key = _factor_key((trace.fingerprint(),), da, exact,
                              model_overhead)
            cached = WAVE_FACTOR_CACHE.get(key, da, (origin,))
            if cached is not None:
                factor, overheads = cached
            else:
                factor = wave_scaling.wave_factor_vec(sub, origin, da,
                                                      exact=exact)
                overheads = None
                if model_overhead:
                    oh_o, oh_d = wave_scaling.dispatch_overheads(origin,
                                                                 da)
                    # store the origin term per-op: the ragged paths
                    # index it by row, and broadcasting the scalar
                    # changes no bits
                    overheads = (np.full(len(t_o), oh_o, np.float64),
                                 oh_d)
                WAVE_FACTOR_CACHE.insert(key, da, (origin,), factor,
                                         overheads)
            out[alike] = wave_scaling.combine_wave_factor(t_o, factor,
                                                          overheads)

    # kernel-varying without an MLP: vectorized analytical fallback
    kind_has_mlp = np.asarray([k in mlps for k in arrays.kinds], bool)
    no_mlp = arrays.kernel_varying & ~kind_has_mlp[arrays.kind_ids]
    if no_mlp.any():
        out[no_mlp] = analytical_ms_vec(arrays, da)[no_mlp]

    _mlp_scores_per_kind(arrays, da, mlps, out,
                         feature_buffers=feature_buffers)

    return FleetPrediction(origin_device=trace.origin_device,
                           dests=list(da.names), op_ms=out, arrays=arrays,
                           label=trace.label)


# ---------------------------------------------------------------------------
# Multi-trace ragged grid: several traces x many devices in one pass.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RaggedTraceArrays:
    """Several traces stacked into one structure-of-arrays.

    Rows ``offsets[i]:offsets[i+1]`` belong to trace ``i``; ``kind_ids``
    index into the *unified* ``kinds`` list (union over all traces), so one
    per-kind MLP batch can span every trace at once.  Per-trace metadata
    (origin device, label, content fingerprint) rides along for the serve
    layer's per-trace result caching."""
    offsets: np.ndarray          # (n_traces + 1,) int64 segment boundaries
    trace_ids: np.ndarray        # (total_ops,) int32 row -> trace index
    origins: List[str]           # (n_traces,) origin device names
    labels: List[str]            # (n_traces,)
    fingerprints: List[str]      # (n_traces,) TrackedTrace.fingerprint()
    flops: np.ndarray            # (total_ops,)
    bytes_accessed: np.ndarray   # (total_ops,)
    intensity: np.ndarray        # (total_ops,)
    measured_ms: np.ndarray      # (total_ops,) NaN where unmeasured
    multiplicity: np.ndarray     # (total_ops,)
    kernel_varying: np.ndarray   # (total_ops,) bool
    kind_ids: np.ndarray         # (total_ops,) int32 into ``kinds``
    kinds: List[str]             # unified kinds, sorted
    op_features: np.ndarray      # (total_ops, 9) raw MLP op features
    _alike_origin: Optional[devices.OriginArrays] = dataclasses.field(
        default=None, repr=False, compare=False)
    _factor_token: Optional[Tuple] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_traces(self) -> int:
        return len(self.origins)

    @property
    def n_ops(self) -> int:
        return int(self.flops.shape[0])

    def segment(self, i: int) -> TraceArrays:
        """Trace ``i``'s rows as a plain :class:`TraceArrays` view."""
        s, e = int(self.offsets[i]), int(self.offsets[i + 1])
        return TraceArrays(
            flops=self.flops[s:e], bytes_accessed=self.bytes_accessed[s:e],
            intensity=self.intensity[s:e],
            measured_ms=self.measured_ms[s:e],
            multiplicity=self.multiplicity[s:e],
            kernel_varying=self.kernel_varying[s:e],
            kind_ids=self.kind_ids[s:e], kinds=self.kinds,
            op_features=self.op_features[s:e])

    def origin_arrays(self) -> devices.OriginArrays:
        """Per-op origin-device arrays for segment-aware wave scaling."""
        specs = [devices.get(o) for o in self.origins]
        return devices.repeat_origins(specs, np.diff(self.offsets))

    def alike_origin_arrays(self) -> devices.OriginArrays:
        """Origin arrays masked to the kernel-alike rows.

        Cached on the stack: the mask is a pure function of the (immutable)
        stacked arrays, and rebuilding it dominated the fixed per-sweep
        cost for small trace stacks."""
        if self._alike_origin is None:
            self._alike_origin = \
                self.origin_arrays().take(~self.kernel_varying)
        return self._alike_origin

    def factor_token(self) -> Tuple:
        """Content identity of this stack for the cross-stack factor
        cache: the tuple of trace fingerprints (a superset of what the
        factor depends on — alike-row arrays and per-trace origins).
        Memoized; a 1-trace stack's token equals ``(fingerprint,)``, the
        same token ``predict_trace_batch`` uses, so single-trace predict
        traffic and 1-trace sweeps share one cache entry."""
        if self._factor_token is None:
            self._factor_token = tuple(self.fingerprints)
        return self._factor_token

    def origin_specs(self) -> Tuple:
        """The per-trace origin ``DeviceSpec`` tuple as currently
        resolved — the factor cache validates entries against it by
        value, since the trace fingerprints name the origin device but
        do not hash its numbers (a monkeypatched/replaced registry entry
        must invalidate, not serve a stale factor)."""
        return tuple(devices.get(o) for o in self.origins)

    def alike_wave_factor(self, da: DeviceArrays, exact: bool,
                          model_overhead: bool):
        """Wave-scaling factor grid for the kernel-alike rows x ``da``:
        (factor (n_alike, n_dev), overheads-or-None).

        The factor is a pure function of this (immutable) stack and the
        destination fleet, so repeat sweeps skip the pow-heavy recompute
        and pay only the ``t * factor`` combine.  Since PR 5 the entry
        lives in the module-level :data:`WAVE_FACTOR_CACHE` keyed by
        content fingerprints — it survives this stack object and also
        serves ``predict_trace_batch`` and freshly-restacked sweeps over
        the same traces.  Stale-spec safety is the cache's validation of
        the destination ``DeviceArrays`` instance and the origin spec
        values (see its docstring)."""
        key = _factor_key(self.factor_token(), da, exact, model_overhead)
        origins = self.origin_specs()
        hit = WAVE_FACTOR_CACHE.get(key, da, origins)
        if hit is not None:
            return hit
        origin = self.alike_origin_arrays()
        alike = ~self.kernel_varying
        sub = SimpleNamespace(intensity=self.intensity[alike],
                              bytes_accessed=self.bytes_accessed[alike])
        factor = wave_scaling.wave_factor_vec(sub, origin, da, exact=exact)
        overheads = (wave_scaling.dispatch_overheads(origin, da)
                     if model_overhead else None)
        WAVE_FACTOR_CACHE.insert(key, da, origins, factor, overheads)
        return factor, overheads

    def peek_wave_factor(self, da: DeviceArrays, exact: bool,
                         model_overhead: bool):
        """The cached factor for ``da`` if warm, else None — masked
        sweeps must not pay a full-grid factor build for partial work
        (and a cold peek is not a counted miss, see the cache's
        ``peek``)."""
        return WAVE_FACTOR_CACHE.peek(
            _factor_key(self.factor_token(), da, exact, model_overhead),
            da, self.origin_specs())

    def extend(self, traces: Sequence[TrackedTrace]) -> "RaggedTraceArrays":
        """Append traces, reusing this stack's arrays for the shared prefix.

        Returns a NEW stack (stacks are immutable once built — the stack
        cache hands one instance to many sweeps).  Concatenating the
        ready prefix with just the new tail produces bit-identical arrays
        to restacking everything: segment data is copied verbatim and the
        unified kind vocabulary is the same sorted union either way."""
        return _concat_stacks(self, _build_stack(list(traces)))


def _concat_stacks(a: RaggedTraceArrays,
                   b: RaggedTraceArrays) -> RaggedTraceArrays:
    if a.kinds == b.kinds:
        kinds, a_ids, b_ids = list(a.kinds), a.kind_ids, b.kind_ids
    else:
        kinds = sorted(set(a.kinds) | set(b.kinds))
        kmap = {k: i for i, k in enumerate(kinds)}
        a_ids = np.asarray([kmap[k] for k in a.kinds],
                           np.int32)[a.kind_ids]
        b_ids = np.asarray([kmap[k] for k in b.kinds],
                           np.int32)[b.kind_ids]
    cat = lambda f: np.concatenate([getattr(a, f), getattr(b, f)])
    return RaggedTraceArrays(
        offsets=np.concatenate([a.offsets, a.offsets[-1] + b.offsets[1:]]),
        trace_ids=np.concatenate([a.trace_ids,
                                  b.trace_ids + np.int32(a.n_traces)]),
        origins=a.origins + b.origins, labels=a.labels + b.labels,
        fingerprints=a.fingerprints + b.fingerprints,
        flops=cat("flops"), bytes_accessed=cat("bytes_accessed"),
        intensity=cat("intensity"), measured_ms=cat("measured_ms"),
        multiplicity=cat("multiplicity"),
        kernel_varying=cat("kernel_varying"),
        kind_ids=np.concatenate([a_ids, b_ids]), kinds=kinds,
        op_features=cat("op_features"))


class _StackCache:
    """Fingerprint-keyed LRU of built :class:`RaggedTraceArrays`.

    Keys are ``((fingerprint, label), ...)`` tuples — the label rides
    along because it is the one piece of sweep output not covered by the
    fingerprint.  An exact hit skips stacking entirely (zero repack); a
    request extending a cached *prefix* reuses the ready prefix arrays
    and only stacks the new tail.  Bounded by entry count AND bytes
    (prefix-extended supersets are independent copies, so an entry-only
    LRU could pin many near-duplicates of a large trace set); the
    process-wide instance reads its bounds from
    ``REPRO_STACK_CACHE_ENTRIES`` / ``REPRO_STACK_CACHE_BYTES``
    (defaults 16 entries / 256 MiB).
    Thread-safe: the serving layer's coalescing leaders stack from
    short-lived threads."""

    def __init__(self, capacity: Optional[int] = None,
                 max_bytes: Optional[int] = None):
        self.capacity = (env_int("REPRO_STACK_CACHE_ENTRIES", 16)
                         if capacity is None else capacity)
        self.max_bytes = (env_int("REPRO_STACK_CACHE_BYTES", 256 << 20)
                          if max_bytes is None else max_bytes)
        self._data: "OrderedDict[Tuple, RaggedTraceArrays]" = OrderedDict()
        self._bytes: Dict[Tuple, int] = {}
        self._total_bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.extends = 0
        self.builds = 0

    @staticmethod
    def _nbytes(stack: RaggedTraceArrays) -> int:
        return sum(getattr(stack, f).nbytes
                   for f in ("offsets", "trace_ids", "flops",
                             "bytes_accessed", "intensity", "measured_ms",
                             "multiplicity", "kernel_varying", "kind_ids",
                             "op_features"))

    def stack(self, traces: List[TrackedTrace]) -> RaggedTraceArrays:
        key = tuple((t.fingerprint(), t.label) for t in traces)
        with self._lock:
            hit = self._data.get(key)
            if hit is not None:
                self._data.move_to_end(key)
                self.hits += 1
                return hit
            best: Optional[Tuple] = None
            for k in self._data:
                if len(k) < len(key) and key[:len(k)] == k \
                        and (best is None or len(k) > len(best)):
                    best = k
            base = self._data[best] if best is not None else None
        if base is not None:
            stack = base.extend(traces[len(best):])
        else:
            stack = _build_stack(traces)
        nbytes = self._nbytes(stack)
        with self._lock:
            self.extends += base is not None
            self.builds += base is None
            if key in self._data:       # racing fill: replace accounting
                self._total_bytes -= self._bytes.pop(key)
            self._data[key] = stack
            self._bytes[key] = nbytes
            self._total_bytes += nbytes
            self._data.move_to_end(key)
            while self._data and (len(self._data) > self.capacity
                                  or self._total_bytes > self.max_bytes):
                old_key, _ = self._data.popitem(last=False)
                self._total_bytes -= self._bytes.pop(old_key)
        return stack

    def stats(self) -> Dict[str, int]:
        """Counter snapshot under the lock (the ``/stats`` payload)."""
        with self._lock:
            return {"hits": self.hits, "extends": self.extends,
                    "builds": self.builds, "entries": len(self._data),
                    "bytes": self._total_bytes,
                    "capacity": self.capacity,
                    "max_bytes": self.max_bytes}

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self._bytes.clear()
            self._total_bytes = 0
            self.hits = self.extends = self.builds = 0

    def export_state(self) -> List[Tuple]:
        """Pickle-safe ``(key, stack)`` snapshot (``serve/snapshot.py``).

        :class:`RaggedTraceArrays` is numpy arrays + string lists all
        the way down (the private memo fields are plain dataclasses of
        the same), so entries pickle as-is."""
        with self._lock:
            return list(self._data.items())

    def import_state(self, entries) -> int:
        """Restore :meth:`export_state` entries (LRU/byte bounds apply).

        Imports do not count as builds — the restored warmth is the
        point, not engine work.  Returns the number restored."""
        restored = 0
        for key, stack in entries:
            nbytes = self._nbytes(stack)
            with self._lock:
                if key in self._data:
                    self._total_bytes -= self._bytes.pop(key)
                self._data[key] = stack
                self._bytes[key] = nbytes
                self._total_bytes += nbytes
                self._data.move_to_end(key)
                while self._data and (len(self._data) > self.capacity
                                      or self._total_bytes > self.max_bytes):
                    old_key, _ = self._data.popitem(last=False)
                    self._total_bytes -= self._bytes.pop(old_key)
            restored += 1
        return restored


#: the process-wide stack cache behind ``stack_traces(cache=True)``
STACK_CACHE = _StackCache()


def stack_traces(traces: Union["RaggedTraceArrays",
                               Sequence[TrackedTrace]],
                 cache: bool = True) -> RaggedTraceArrays:
    """Stack several :class:`TrackedTrace` into one ragged SoA.

    Idempotent (a ready :class:`RaggedTraceArrays` passes through), so hot
    callers can stack once and sweep many times.  With ``cache=True``
    (the default) the build is memoized in the process-wide
    :data:`STACK_CACHE` keyed by trace fingerprints: repeat sweeps over
    the same (or a superset of a cached) trace list skip the
    ``np.concatenate`` repack entirely.  ``cache=False`` forces a fresh
    build (benchmark baseline / kill switch)."""
    if isinstance(traces, RaggedTraceArrays):
        return traces
    traces = list(traces)
    if not traces:
        raise ValueError("stack_traces needs at least one trace")
    if cache:
        for t in traces:        # validate before keying the cache
            if t.to_arrays().n_ops == 0:
                raise ValueError(f"trace {t.label!r} has no ops")
        return STACK_CACHE.stack(traces)
    return _build_stack(traces)


def _build_stack(traces: List[TrackedTrace]) -> RaggedTraceArrays:
    if not traces:
        raise ValueError("stack_traces needs at least one trace")
    per = [t.to_arrays() for t in traces]
    for t, p in zip(traces, per):
        if p.n_ops == 0:
            raise ValueError(f"trace {t.label!r} has no ops")
    lengths = np.asarray([p.n_ops for p in per], np.int64)
    offsets = np.zeros(len(per) + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    cat = lambda field: np.concatenate([getattr(p, field) for p in per])
    if all(p.kinds == per[0].kinds for p in per[1:]):
        # fast path: one shared kind vocabulary (the common serving case —
        # traces of one model family), no per-trace id remap needed
        kinds = list(per[0].kinds)
        kind_ids = cat("kind_ids")
    else:
        kinds = sorted(set().union(*(p.kinds for p in per)))
        kmap = {k: i for i, k in enumerate(kinds)}
        kind_ids = np.concatenate([
            np.asarray([kmap[k] for k in p.kinds], np.int32)[p.kind_ids]
            for p in per])
    return RaggedTraceArrays(
        offsets=offsets,
        trace_ids=np.repeat(np.arange(len(per), dtype=np.int32), lengths),
        origins=[t.origin_device for t in traces],
        labels=[t.label for t in traces],
        fingerprints=[t.fingerprint() for t in traces],
        flops=cat("flops"), bytes_accessed=cat("bytes_accessed"),
        intensity=cat("intensity"), measured_ms=cat("measured_ms"),
        multiplicity=cat("multiplicity"),
        kernel_varying=cat("kernel_varying"),
        kind_ids=kind_ids, kinds=kinds, op_features=cat("op_features"))


@dataclasses.dataclass
class SweepPrediction:
    """The (n_traces x n_devices) what-if grid of one ragged sweep."""
    dests: List[str]
    op_ms: np.ndarray            # (total_ops, n_dev)
    arrays: RaggedTraceArrays
    _totals: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_traces(self) -> int:
        return self.arrays.n_traces

    @property
    def labels(self) -> List[str]:
        return self.arrays.labels

    @property
    def total_ms(self) -> np.ndarray:
        """Iteration time grid, shape (n_traces, n_dev).

        One ``np.add.reduceat`` over the segment offsets instead of a
        per-trace Python loop; ``FleetPrediction.total_ms`` uses the same
        strictly-sequential reduceat accumulation, so row i stays
        bit-identical to predicting trace i alone at any segment length.
        Cell-masked sweeps leave NaN in uncomputed cells, which the
        reduction propagates — readers must only consult computed cells.
        Memoized: cell-by-cell readers (``time_for``) must not re-reduce
        the grid per access."""
        if self._totals is None:
            weighted = self.op_ms * self.arrays.multiplicity[:, None]
            self._totals = np.add.reduceat(weighted,
                                           self.arrays.offsets[:-1], axis=0)
        return self._totals

    def row(self, i: int) -> FleetPrediction:
        """Trace ``i``'s slice as a full :class:`FleetPrediction`."""
        s, e = int(self.arrays.offsets[i]), int(self.arrays.offsets[i + 1])
        return FleetPrediction(origin_device=self.arrays.origins[i],
                               dests=list(self.dests),
                               op_ms=self.op_ms[s:e],
                               arrays=self.arrays.segment(i),
                               label=self.arrays.labels[i])

    def time_for(self, i: int, dest: str) -> float:
        return float(self.total_ms[i, self.dests.index(dest)])

    def as_dicts(self) -> List[Dict[str, float]]:
        # one C-level tolist() for the whole grid, not one per trace
        return [dict(zip(self.dests, row)) for row in self.total_ms.tolist()]


class FusedMLPScorer:
    """Packs all op-kind MLPs for the one-launch Pallas scorer.

    The per-kind jitted forwards pay one dispatch per kind per sweep; this
    scorer groups all kernel-varying feature rows by kind, pads each group
    to whole ``block_m`` row blocks, and evaluates everything in a single
    ``kernels.ops.fused_mlp_score`` call (compiled Pallas on TPU,
    interpret-mode or the jnp oracle on CPU).

    Requires every packed MLP to share one architecture — true for
    ``train_mlps`` output, which trains all four kinds with one config.
    """

    def __init__(self, mlps: Dict, block_m: int = 128, impl: str = "auto"):
        from repro.core import mlp as mlp_mod
        from repro.kernels import ops as kernel_ops
        import jax.numpy as jnp
        if not mlps:
            raise ValueError("FusedMLPScorer needs at least one MLP")
        self.kinds = sorted(mlps)
        arches = {(m.cfg.hidden_layers, m.cfg.hidden_size,
                   m.params[0][0].shape[0]) for m in mlps.values()}
        if len(arches) != 1:
            raise ValueError(
                f"fused scorer needs architecture-uniform MLPs, got "
                f"{sorted(arches)}")
        _, self.hidden, self.in_features = arches.pop()
        ws, bs = [], []
        for kind in self.kinds:
            w, b = kernel_ops.pack_mlp_params(
                mlps[kind].params, self.in_features, self.hidden)
            ws.append(w)
            bs.append(b)
        self.weights = jnp.stack(ws)          # (K, L, H, H)
        self.biases = jnp.stack(bs)           # (K, L, H)
        self.mlps = dict(mlps)                # normalization + output contract
        self.block_m = block_m
        self.impl = impl
        #: the JAX devices this scorer's outputs were computed on, one
        #: entry per device that ran at least one of its launches
        self.ran_on: set = set()
        # the row-mapped path standardizes per row via these stacked
        # normalization constants (one vectorized expression, elementwise
        # identical to per-kind normalize()); MLPs with an overridden
        # normalize/ms_from_log keep the per-kind loops instead
        self._stock_contract = all(
            type(m).normalize is mlp_mod.TrainedMLP.normalize
            and type(m).ms_from_log is mlp_mod.TrainedMLP.ms_from_log
            for m in mlps.values())
        if self._stock_contract:
            self._feat_mean = np.stack(
                [np.asarray(mlps[k].feature_mean) for k in self.kinds])
            self._feat_std = np.stack(
                [np.asarray(mlps[k].feature_std) for k in self.kinds])

    def _host(self, out) -> np.ndarray:
        """A launch's output on the host, noting the device it ran on."""
        self.ran_on.update(out.devices())
        return np.asarray(out)

    def score_ms(self, feats_by_kind: Dict[str, np.ndarray]
                 ) -> Dict[str, np.ndarray]:
        """Raw feature rows per kind -> predicted ms per kind, one launch.

        The block count is padded to a jit bucket
        (:func:`repro.kernels.fused_mlp_score.bucket_blocks`) before the
        launch: coalesced service batches arrive at arbitrary sizes, and
        without bucketing every distinct size would recompile the jitted
        scorer.  Padding blocks carry zero rows through MLP 0 and are
        sliced off before un-logging."""
        from repro.kernels import ops as kernel_ops
        from repro.kernels.fused_mlp_score import bucket_blocks
        import jax.numpy as jnp
        if not any(f.shape[0] for f in feats_by_kind.values()):
            # bucket_blocks(0) == 0 by contract: never launch an empty
            # kernel — answer the degenerate query directly instead
            return {kind: self.mlps[kind].ms_from_log(
                        np.zeros(0, np.float32))
                    for kind in feats_by_kind}
        bm = self.block_m
        blocks, kind_of_block, counts = [], [], []
        for kind, feats in feats_by_kind.items():
            x = self.mlps[kind].normalize(feats)
            n = x.shape[0]
            nb = -(-n // bm)
            xp = np.zeros((nb * bm, self.hidden), np.float32)
            xp[:n, :x.shape[1]] = x
            blocks.append(xp)
            kind_of_block.extend([self.kinds.index(kind)] * nb)
            counts.append(n)
        pad_blocks = bucket_blocks(len(kind_of_block)) - len(kind_of_block)
        if pad_blocks:
            blocks.append(np.zeros((pad_blocks * bm, self.hidden),
                                   np.float32))
            kind_of_block.extend([0] * pad_blocks)
        SCORER_DISPATCHES.bump("fused")
        SCORER_ROWS.add(sum(counts), len(kind_of_block) * bm)
        log_ms = self._host(kernel_ops.fused_mlp_score(
            jnp.asarray(np.concatenate(blocks)),
            jnp.asarray(np.asarray(kind_of_block, np.int32)),
            self.weights, self.biases, block_m=bm, impl=self.impl))
        out, offset = {}, 0
        for kind, n in zip(feats_by_kind, counts):
            out[kind] = self.mlps[kind].ms_from_log(
                log_ms[offset:offset + n])
            offset += (-(-n // bm)) * bm
        return out

    def _normalized_rows(self, feats: np.ndarray,
                         kind_ids: np.ndarray) -> np.ndarray:
        """Per-row standardized features, (m, n_raw_feat) float64.

        One vectorized expression over gathered per-kind constants for
        stock ``TrainedMLP``s — elementwise identical bits to
        ``normalize()`` on per-kind slices — and the per-kind loop for
        anything with an overridden contract."""
        if self._stock_contract:
            return ((np.atleast_2d(feats) - self._feat_mean[kind_ids])
                    / self._feat_std[kind_ids])
        out = np.empty(np.atleast_2d(feats).shape, np.float64)
        for ki, kind in enumerate(self.kinds):
            rows = np.flatnonzero(kind_ids == ki)
            if len(rows):
                out[rows] = self.mlps[kind].normalize(feats[rows])
        return out

    def _ms_from_log_rows(self, log_ms: np.ndarray,
                          kind_ids: np.ndarray) -> np.ndarray:
        """Per-row output contract: one vectorized un-log for stock
        MLPs (``ms_from_log`` is one shared static function), per kind
        otherwise."""
        if self._stock_contract:
            from repro.core.mlp import TrainedMLP
            return np.asarray(TrainedMLP.ms_from_log(log_ms), np.float64)
        out = np.empty(log_ms.shape[0], np.float64)
        for ki, kind in enumerate(self.kinds):
            rows = np.flatnonzero(kind_ids == ki)
            if len(rows):
                out[rows] = self.mlps[kind].ms_from_log(log_ms[rows])
        return out

    def score_rows_ms(self, feats: np.ndarray,
                      kind_ids: np.ndarray) -> np.ndarray:
        """Raw feature rows in ANY kind order -> predicted ms, one launch.

        ``kind_ids[i]`` indexes ``self.kinds`` for row ``i`` — callers
        need no per-kind grouping, so the cell-masked pair path costs
        exactly ONE scorer dispatch however many op kinds its cold cells
        mix.  Two lowerings behind the same contract:

        * Pallas/interpret: the row-mapped kernel
          (:func:`~repro.kernels.fused_mlp_score.fused_mlp_score_rows`)
          with scalar-prefetched kind maps — rows stay in caller order,
          padded to a ``bucket_blocks`` jit bucket (padding rides kind
          0, garbage by contract, sliced off);
        * jnp (the CPU backend): rows are regrouped by kind host-side
          into a (K, bucket_rows(max), H) stack and scored by ONE
          K-batched jitted gemm chain — on CPU there is no DMA schedule
          to preserve, and skipping the kernel's every-kind-per-row
          select work keeps the single dispatch cheaper than even one
          per-kind forward of the same rows.

        Normalization and the output contract stay per kind, shared
        with ``predict_ms``."""
        from repro.kernels import ops as kernel_ops
        from repro.kernels.fused_mlp_score import (bucket_blocks,
                                                  bucket_rows)
        import jax.numpy as jnp
        kind_ids = np.asarray(kind_ids, np.int32)
        m = feats.shape[0]
        if m == 0:
            return np.zeros(0, np.float64)
        xn = self._normalized_rows(feats, kind_ids)
        impl = kernel_ops._resolve(self.impl)
        SCORER_DISPATCHES.bump("fused")
        if impl == "jnp":
            rows_by_kind = [np.flatnonzero(kind_ids == ki)
                            for ki in range(len(self.kinds))]
            bpad = bucket_rows(max(len(r) for r in rows_by_kind))
            xs = np.zeros((len(self.kinds), bpad, self.hidden), np.float32)
            for ki, rows in enumerate(rows_by_kind):
                xs[ki, :len(rows), :xn.shape[1]] = xn[rows]
            SCORER_ROWS.add(m, xs.shape[0] * bpad)
            log_grid = self._host(kernel_ops.fused_mlp_score_stacked(
                jnp.asarray(xs), self.weights, self.biases))
            log_ms = np.empty(m, np.float32)
            for ki, rows in enumerate(rows_by_kind):
                log_ms[rows] = log_grid[ki, :len(rows)]
        else:
            bm = self.block_m
            padded = bucket_blocks(-(-m // bm)) * bm
            xp = np.zeros((padded, self.hidden), np.float32)
            row_kinds = np.zeros(padded, np.int32)
            row_kinds[:m] = kind_ids
            xp[:m, :xn.shape[1]] = xn
            SCORER_ROWS.add(m, padded)
            log_ms = self._host(kernel_ops.fused_mlp_score_rows(
                jnp.asarray(xp), jnp.asarray(row_kinds), self.weights,
                self.biases, block_m=bm, impl=impl))[:m]
        return self._ms_from_log_rows(log_ms, kind_ids)


def _resolve_scorer(scorer, mlps: Dict):
    """Map a ``predict_sweep`` scorer spelling to a usable instance.

    ``None``/"off" -> per-kind jitted forwards; "auto" -> the fused
    Pallas scorer on a TPU backend (and per-kind forwards elsewhere, which
    keeps strict parity with ``predict_fleet`` on CPU); an impl name
    ("pallas" | "interpret" | "jnp") forces the fused path; a ready
    :class:`FusedMLPScorer` is used as-is.  A fused scorer that cannot be
    built (MLPs of mixed architectures) raises: on a TPU, "auto" never
    quietly runs the per-kind forwards in the kernel's place.  The single
    policy shared by ``predict_sweep`` and ``HabitatPredictor`` (which
    only adds caching).
    """
    if scorer is None or scorer == "off" or not mlps:
        return None
    if isinstance(scorer, FusedMLPScorer):
        return scorer
    if scorer == "auto":
        import jax
        if jax.default_backend() != "tpu":
            return None
        return FusedMLPScorer(mlps, impl="pallas")
    if scorer in ("pallas", "interpret", "jnp"):
        return FusedMLPScorer(mlps, impl=scorer)
    raise ValueError(f"unknown scorer spelling {scorer!r}")


def predict_sweep(traces: Union[RaggedTraceArrays, Sequence[TrackedTrace]],
                  dests: Union[DeviceArrays, Sequence[str],
                               Sequence[DeviceSpec]],
                  mlps: Optional[Dict] = None,
                  exact: bool = False,
                  model_overhead: bool = False,
                  scorer=None,
                  cell_mask: Optional[np.ndarray] = None,
                  stack_cache: bool = True,
                  feature_buffers: bool = True,
                  factor_cache: bool = True) -> SweepPrediction:
    """Predict every trace on every destination in one ragged pass.

    Row i of the result reproduces :func:`predict_trace_batch` on trace i
    alone.  Wave scaling broadcasts per-op origin arrays through the same
    IEEE expression and the analytical fallback is the same element-wise
    grid function, so those rows agree BITWISE.  Trained-MLP rows go
    through the same per-kind batched forwards (when no fused ``scorer``
    is active) but batch all traces' ops together, so their jitted
    float32 batches pad to different shapes than the per-trace spelling —
    equal to ~1e-6 relative, not bit-for-bit.

    ``cell_mask`` — bool (n_traces, n_dev), True = compute — enables
    partial-compute sweeps: only masked-in cells are evaluated (wave
    scaling and the analytical fallback via flat element-wise gathers,
    bitwise-equal to the full grid; MLP rows via pair-gathered feature
    rows, tolerance-equal like any re-batched MLP forward), and every
    masked-out cell is left NaN.  The serve layer uses this to fill only
    the cache-cold cells of a sweep.  ``stack_cache``/``feature_buffers``/
    ``factor_cache`` select the zero-repack stack cache, the pooled
    feature buffers, and the cross-stack wave-factor cache (defaults on;
    all off is the allocate-and-recompute-everything compat spelling —
    ``factor_cache=False`` matters for baselines because the factor
    cache is content-keyed and would otherwise stay warm across even a
    fresh restack).
    """
    ragged = stack_traces(traces, cache=stack_cache)
    da = devices.as_arrays(dests)
    mlps = mlps or {}
    if cell_mask is not None:
        cell_mask = np.asarray(cell_mask, bool)
        if cell_mask.shape != (ragged.n_traces, da.n):
            raise ValueError(
                f"cell_mask shape {cell_mask.shape} != "
                f"(n_traces, n_dev) = {(ragged.n_traces, da.n)}")
        if cell_mask.all():
            cell_mask = None    # the full grid is the fast spelling
    if cell_mask is not None:
        return _predict_sweep_masked(ragged, da, mlps, exact,
                                     model_overhead, scorer, cell_mask,
                                     feature_buffers=feature_buffers,
                                     factor_cache=factor_cache)
    out = np.empty((ragged.n_ops, da.n), np.float64)

    # kernel-alike: segment-aware wave scaling over the whole ragged
    # grid, with the t-independent factor served from the cross-stack
    # cache — a repeat sweep pays only the t * factor combine
    alike = ~ragged.kernel_varying
    if alike.any():
        t_o = ragged.measured_ms[alike]
        if np.isnan(t_o).any():
            _raise_unmeasured(ragged, np.flatnonzero(alike), t_o)
        if factor_cache:
            factor, overheads = ragged.alike_wave_factor(da, exact,
                                                         model_overhead)
            out[alike] = wave_scaling.combine_wave_factor(t_o, factor,
                                                          overheads)
        else:
            sub = SimpleNamespace(
                intensity=ragged.intensity[alike],
                bytes_accessed=ragged.bytes_accessed[alike])
            out[alike] = wave_scaling.scale_times_vec(
                t_o, sub, ragged.alike_origin_arrays(), da, exact=exact,
                model_overhead=model_overhead)

    # kernel-varying without an MLP: vectorized analytical fallback,
    # computed on the masked rows only (the formula is element-wise, so
    # this matches predict_trace_batch's full-grid-then-mask bitwise)
    no_mlp = _no_mlp_rows(ragged, mlps)
    if no_mlp.any():
        sub = SimpleNamespace(
            kernel_varying=ragged.kernel_varying[no_mlp],
            flops=ragged.flops[no_mlp],
            bytes_accessed=ragged.bytes_accessed[no_mlp])
        out[no_mlp] = analytical_ms_vec(sub, da)

    # kernel-varying with an MLP: fused one-launch scorer when available,
    # otherwise the same per-kind batched forwards as predict_trace_batch
    fused = _resolve_scorer(scorer, mlps)
    if fused is not None:
        feats_by_kind: Dict[str, np.ndarray] = {}
        idx_by_kind: Dict[str, np.ndarray] = {}
        bufs: List[np.ndarray] = []
        dev_t = (dataset_mod.transform_features(da.feature_matrix)
                 if feature_buffers else None)
        n_feat = ragged.op_features.shape[1] + da.feature_matrix.shape[1]
        try:
            for kind, idx in _mlp_kind_rows(ragged, mlps):
                idx_by_kind[kind] = idx
                if feature_buffers:
                    op_t = dataset_mod.transform_features(
                        ragged.op_features[idx])
                    buf = _FEATURE_BUFFERS.acquire(len(idx) * da.n, n_feat)
                    bufs.append(buf)
                    feats_by_kind[kind] = _features_grid_into(buf, op_t,
                                                              dev_t)
                else:
                    feats_by_kind[kind] = mlp_features_grid(ragged, idx, da)
            if feats_by_kind:
                with telemetry.span("engine.score"):
                    scored = fused.score_ms(feats_by_kind)
                for kind, idx in idx_by_kind.items():
                    out[idx] = scored[kind].reshape(len(idx), da.n)
        finally:
            for buf in bufs:
                _FEATURE_BUFFERS.release(buf)
    else:
        _mlp_scores_per_kind(ragged, da, mlps, out,
                             feature_buffers=feature_buffers)

    return SweepPrediction(dests=list(da.names), op_ms=out, arrays=ragged)


def _no_mlp_rows(ragged: RaggedTraceArrays, mlps: Dict) -> np.ndarray:
    kind_has_mlp = np.asarray([k in mlps for k in ragged.kinds], bool)
    return ragged.kernel_varying & ~kind_has_mlp[ragged.kind_ids]


def _raise_unmeasured(ragged: RaggedTraceArrays, rows: np.ndarray,
                      t_o: np.ndarray) -> None:
    bad = int(rows[np.isnan(t_o).argmax()])
    tid = int(ragged.trace_ids[bad])
    raise ValueError(
        f"trace {ragged.labels[tid]!r} op row "
        f"{bad - int(ragged.offsets[tid])} has no origin measurement")


#: mask-row pattern count up to which the masked sweep computes broadcast
#: subgrids per pattern group instead of per-cell gathers.  Production
#: warm structure clusters into a handful of patterns (clients warm a few
#: distinct fleets), where subgrids skip all gather/scatter overhead; a
#: fully random mask degenerates to one pattern per trace, where the flat
#: per-cell path wins.
_PATTERN_GROUP_LIMIT = 8


def _predict_sweep_masked(ragged: RaggedTraceArrays, da: DeviceArrays,
                          mlps: Dict, exact: bool, model_overhead: bool,
                          scorer, cell_mask: np.ndarray,
                          feature_buffers: bool = True,
                          factor_cache: bool = True) -> SweepPrediction:
    """Partial-compute sweep: evaluate only the masked-in cells.

    Every computed cell reproduces the full-grid value — bitwise on the
    wave-scaling/analytical paths (both the pattern-grouped subgrids and
    the flat per-cell gathers run the identical element-wise
    expressions), to MLP-forward tolerance on trained-MLP cells (pair
    batches pad differently, same caveat as any re-batched forward).
    Masked-out cells stay NaN; callers (the planner's cell-level cache
    fill) must only read computed cells."""
    out = np.full((ragged.n_ops, da.n), np.nan)
    op_mask = cell_mask[ragged.trace_ids]            # (n_ops, n_dev)
    patterns, inverse = np.unique(cell_mask, axis=0, return_inverse=True)
    inverse = np.asarray(inverse).reshape(-1)   # numpy 2.0 axis quirk
    grouped = len(patterns) <= _PATTERN_GROUP_LIMIT
    ao = ragged.alike_origin_arrays()
    alike_ops = ~ragged.kernel_varying
    no_mlp_ops = _no_mlp_rows(ragged, mlps)

    cached = (ragged.peek_wave_factor(da, exact, model_overhead)
              if factor_cache else None)
    if grouped:
        # position of each global op row inside the alike subset (the
        # origin arrays are stored alike-subset-major)
        alike_index = np.cumsum(alike_ops) - 1
        for p, pattern in enumerate(patterns):
            cols = np.flatnonzero(pattern)
            if not len(cols):
                continue
            in_group = (inverse == p)[ragged.trace_ids]
            da_sub = da.take(cols)
            rows = np.flatnonzero(in_group & alike_ops)
            if len(rows):
                t_o = ragged.measured_ms[rows]
                if np.isnan(t_o).any():
                    _raise_unmeasured(ragged, rows, t_o)
                pos = alike_index[rows]
                if cached is not None:
                    # warm factor: slice the cached grid (same elements,
                    # so the combine stays bitwise) instead of re-deriving
                    factor, overheads = cached
                    f_sub = factor[np.ix_(pos, cols)]
                    oh = (None if overheads is None else
                          (overheads[0][pos], overheads[1][cols]))
                    out[np.ix_(rows, cols)] = \
                        wave_scaling.combine_wave_factor(t_o, f_sub, oh)
                else:
                    sub = SimpleNamespace(
                        intensity=ragged.intensity[rows],
                        bytes_accessed=ragged.bytes_accessed[rows])
                    origin_sub = devices.OriginArrays(
                        kinds=([ao.kinds[i] for i in pos]
                               if model_overhead else []),
                        mem_bandwidth=ao.mem_bandwidth[pos],
                        clock_hz=ao.clock_hz[pos],
                        wave_size=ao.wave_size[pos])
                    out[np.ix_(rows, cols)] = wave_scaling.scale_times_vec(
                        t_o, sub, origin_sub, da_sub, exact=exact,
                        model_overhead=model_overhead)
            rows = np.flatnonzero(in_group & no_mlp_ops)
            if len(rows):
                sub = SimpleNamespace(
                    kernel_varying=ragged.kernel_varying[rows],
                    flops=ragged.flops[rows],
                    bytes_accessed=ragged.bytes_accessed[rows])
                out[np.ix_(rows, cols)] = analytical_ms_vec(sub, da_sub)
    else:
        # kernel-alike cells: flat element-wise wave scaling
        alike_rows = np.flatnonzero(alike_ops)
        if len(alike_rows):
            r, c = np.nonzero(op_mask[alike_rows])
            if len(r):
                rows = alike_rows[r]
                t_cells = ragged.measured_ms[rows]
                if np.isnan(t_cells).any():
                    _raise_unmeasured(ragged, rows, t_cells)
                if cached is not None:
                    factor, overheads = cached
                    f_cells = factor[r, c]
                    if overheads is None:
                        out[rows, c] = t_cells * f_cells
                    else:
                        oh_o, oh_d = overheads
                        out[rows, c] = (np.maximum(t_cells - oh_o[r], 0.0)
                                        * f_cells + oh_d[c])
                else:
                    sub = SimpleNamespace(
                        intensity=ragged.intensity[rows],
                        bytes_accessed=ragged.bytes_accessed[rows])
                    # gather origin fields directly: OriginArrays.take
                    # would materialize a per-cell Python list of kind
                    # strings, which only the overhead model reads
                    origin_cells = devices.OriginArrays(
                        kinds=([ao.kinds[i] for i in r]
                               if model_overhead else []),
                        mem_bandwidth=ao.mem_bandwidth[r],
                        clock_hz=ao.clock_hz[r], wave_size=ao.wave_size[r])
                    out[rows, c] = wave_scaling.scale_times_flat(
                        t_cells, sub, origin_cells, da, c, exact=exact,
                        model_overhead=model_overhead)

        # kernel-varying cells without an MLP: flat analytical fallback
        no_mlp_rows = np.flatnonzero(no_mlp_ops)
        if len(no_mlp_rows):
            r, c = np.nonzero(op_mask[no_mlp_rows])
            if len(r):
                rows = no_mlp_rows[r]
                sub = SimpleNamespace(
                    kernel_varying=ragged.kernel_varying[rows],
                    flops=ragged.flops[rows],
                    bytes_accessed=ragged.bytes_accessed[rows])
                out[rows, c] = analytical_ms_flat(sub, da, c)

    # kernel-varying cells with an MLP: pair-gathered feature rows.
    # With a fused scorer active, every kind's cold pairs are scored by
    # ONE row-mapped launch (each row carries its own kind id) — no
    # per-kind grouping, no per-kind block padding, exactly 1 scorer
    # dispatch for any kind mix.  Without one (the CPU "auto" default),
    # the PR 4 per-kind forwards run — kept as the parity baseline and
    # the bench_dispatch comparison point.
    fused = _resolve_scorer(scorer, mlps)
    dev_t = dataset_mod.transform_features(da.feature_matrix)
    n_feat = ragged.op_features.shape[1] + da.feature_matrix.shape[1]
    pairs: List[Tuple[str, np.ndarray, np.ndarray, np.ndarray]] = []
    for kind, idx in _mlp_kind_rows(ragged, mlps):
        r, c = np.nonzero(op_mask[idx])
        if len(r):
            pairs.append((kind, idx, r, c))

    def pair_features(buf, idx, r, c):
        # transform only rows that actually appear in cold pairs — work
        # stays proportional to cold cells, not to the kind's full op
        # count (log1p per row is identical either way)
        used, r_used = np.unique(r, return_inverse=True)
        op_t = dataset_mod.transform_features(ragged.op_features[idx[used]])
        return _features_pairs_into(buf, op_t, dev_t, r_used, c)

    if pairs and fused is not None:
        total = sum(len(r) for _, _, r, _ in pairs)
        buf = (_FEATURE_BUFFERS.acquire(total, n_feat) if feature_buffers
               else np.empty((total, n_feat), np.float32))
        try:
            kind_rows = np.empty(total, np.int32)
            offset = 0
            for kind, idx, r, c in pairs:
                pair_features(buf[offset:offset + len(r)], idx, r, c)
                kind_rows[offset:offset + len(r)] = fused.kinds.index(kind)
                offset += len(r)
            with telemetry.span("engine.score"):
                scored = fused.score_rows_ms(buf[:total], kind_rows)
        finally:
            if feature_buffers:
                _FEATURE_BUFFERS.release(buf)
        offset = 0
        for kind, idx, r, c in pairs:
            out[idx[r], c] = scored[offset:offset + len(r)]
            offset += len(r)
    elif pairs:
        for kind, idx, r, c in pairs:
            buf = (_FEATURE_BUFFERS.acquire(len(r), n_feat)
                   if feature_buffers
                   else np.empty((len(r), n_feat), np.float32))
            try:
                feats = pair_features(buf, idx, r, c)
                SCORER_DISPATCHES.bump("per_kind")
                out[idx[r], c] = mlps[kind].predict_ms(feats)
            finally:
                if feature_buffers:
                    _FEATURE_BUFFERS.release(buf)

    return SweepPrediction(dests=list(da.names), op_ms=out, arrays=ragged)
