"""Operation tracking: the JAX analogue of Habitat's ``OperationTracker``.

The paper intercepts PyTorch operations by monkey-patching (Sec. 4.1).  In
JAX the computation graph is *first class*: tracing a step function yields a
jaxpr whose equations are exactly the operations that will run.  We walk the
jaxpr (recursing through jit/remat/cond, and through scan with
multiplicity) and produce a :class:`TrackedTrace` — an ordered list of
:class:`Op` records, each carrying its analytical cost (flops/bytes), its
MLP feature vector, and its kernel-alike/kernel-varying classification.

Listing-1-compatible usage::

    tracker = OperationTracker(origin_device="cpu-host")
    trace = tracker.track(train_step, params, batch)
    print(trace.to_device("tpu-v5e").run_time_ms)
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import math
import numbers
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend import core as jcore

from repro import telemetry
from repro.core import costmodel, devices
from repro.core.costmodel import OpCost

# Operation kinds.  The first four match the paper's kernel-varying set
# (Table 1); ``recurrent`` covers LSTM *and* other matmul-carrying scans
# (e.g. Mamba2's SSD recurrence), which are kernel-varying on TPUs because
# Mosaic/XLA retile them per generation.
KERNEL_VARYING_KINDS = ("conv2d", "linear", "bmm", "recurrent")

#: call-like primitives whose sub-jaxpr is walked in place (JAX 0.9 names
#: a nested ``jax.jit`` call ``jit`` and a ``jax.checkpoint`` ``remat2``)
_HIGHER_ORDER = ("jit", "pjit", "closed_call", "custom_jvp_call",
                 "custom_vjp_call", "remat", "remat2", "checkpoint",
                 "named_call", "core_call", "custom_vjp_call_jaxpr",
                 "custom_lin")
#: ops that only relabel an array (layout, dtype, a slice of it): a
#: matmul operand reached through them is still that array
_RELABEL = ("reshape", "transpose", "convert_element_type", "squeeze",
            "expand_dims", "broadcast_in_dim", "copy", "copy_p", "slice",
            "dynamic_slice")


@dataclasses.dataclass
class Op:
    """One tracked operation (≈ one GPU kernel launch in the paper)."""
    name: str                       # primitive name
    kind: str                       # conv2d | linear | bmm | recurrent | <prim>
    cost: OpCost
    multiplicity: int = 1           # how many times it runs per iteration
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    in_shapes: Tuple[Tuple[int, ...], ...] = ()
    out_shapes: Tuple[Tuple[int, ...], ...] = ()
    dtype: str = "float32"
    measured_ms: Optional[float] = None   # T_o on the origin device
    predicted_ms: Optional[float] = None  # T_d after scaling

    @property
    def kernel_varying(self) -> bool:
        return self.kind in KERNEL_VARYING_KINDS

    def to_dict(self) -> Dict[str, Any]:
        """JSON-safe record (golden-trace files, service wire format).

        Every numeric field is coerced to a native Python number, so an
        op whose times/costs came back as numpy scalars (calibration,
        array math) still serializes — and Python floats round-trip
        through ``json`` bitwise (shortest-repr encoding)."""
        return {
            "name": self.name, "kind": self.kind,
            "cost": {"flops": float(self.cost.flops),
                     "bytes_read": float(self.cost.bytes_read),
                     "bytes_written": float(self.cost.bytes_written)},
            "multiplicity": int(self.multiplicity),
            "params": {str(k): _json_safe(v)
                       for k, v in self.params.items()},
            "in_shapes": [[int(x) for x in s] for s in self.in_shapes],
            "out_shapes": [[int(x) for x in s] for s in self.out_shapes],
            "dtype": self.dtype,
            "measured_ms": _json_safe(self.measured_ms),
            "predicted_ms": _json_safe(self.predicted_ms),
        }

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Op":
        """Decode one op document, validating every field.

        Raises :class:`TraceValidationError` on any malformed input;
        valid documents decode bitwise-identically to the pre-validation
        decoder (``float``/``int`` coercion semantics unchanged)."""
        if not isinstance(d, dict):
            raise TraceValidationError(
                f"op document must be an object, got {type(d).__name__}")
        try:
            name, kind, dtype = d["name"], d["kind"], d["dtype"]
            cost_doc, raw_params = d["cost"], d["params"]
            raw_in, raw_out = d["in_shapes"], d["out_shapes"]
            raw_mult = d["multiplicity"]
            raw_measured, raw_predicted = d["measured_ms"], d["predicted_ms"]
        except KeyError as e:
            raise TraceValidationError(
                f"op document missing field {e}") from None
        name = _v_str(name, "op.name")
        kind = _v_str(kind, "op.kind")
        dtype = _v_str(dtype, "op.dtype")
        if not isinstance(cost_doc, dict):
            raise TraceValidationError(
                f"op.cost must be an object, got {type(cost_doc).__name__}")
        if not isinstance(raw_params, dict):
            raise TraceValidationError(
                f"op.params must be an object, "
                f"got {type(raw_params).__name__}")
        for key, _ in _FEATURE_LAYOUT.get(kind, ()):
            if key in raw_params:
                _v_num(raw_params[key], f"op.params.{key}")
        return Op(
            name=name, kind=kind,
            cost=OpCost(
                flops=_v_num(cost_doc.get("flops"), "op.cost.flops"),
                bytes_read=_v_num(cost_doc.get("bytes_read"),
                                  "op.cost.bytes_read"),
                bytes_written=_v_num(cost_doc.get("bytes_written"),
                                     "op.cost.bytes_written")),
            multiplicity=_v_num(raw_mult, "op.multiplicity",
                                integral=True),
            params=dict(raw_params),
            in_shapes=_v_shapes(raw_in, "op.in_shapes"),
            out_shapes=_v_shapes(raw_out, "op.out_shapes"),
            dtype=dtype,
            measured_ms=_v_num(raw_measured, "op.measured_ms",
                               allow_none=True),
            predicted_ms=_v_num(raw_predicted, "op.predicted_ms",
                                allow_none=True))

    def feature_vector(self) -> List[float]:
        """Kind-specific op features for the MLP predictors (Sec. 3.4).

        The paper's per-kind layer dimensions (Table 1), padded to length 7,
        plus the op's analytical FLOPs and bytes.  The two cost features are
        an addition over the paper: in JAX a "kind" covers heterogeneous
        jaxpr patterns (e.g. ``recurrent`` spans LSTM, GRU and SSD scans),
        so the dimensions alone do not determine the work performed."""
        layout = _FEATURE_LAYOUT.get(self.kind)
        if layout is None:
            f = [self.cost.intensity, 0, 0, 0, 0, 0, 0]
        else:
            p = self.params
            f = ([p.get(key, default) for key, default in layout]
                 + [0] * (7 - len(layout)))
        f = f + [self.cost.flops, self.cost.bytes_accessed]
        return [float(x) for x in f]


def _json_safe(v: Any) -> Any:
    """Coerce an op-params value into something ``json.dump`` accepts."""
    if isinstance(v, (bool, str)) or v is None:
        return v
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        return float(v)
    if isinstance(v, (tuple, list)):
        return [_json_safe(x) for x in v]
    return str(v)


class TraceValidationError(ValueError):
    """A trace wire document failed strict validation.

    The ONE exception type ``Op.from_dict`` / ``TrackedTrace.from_dict``
    / ``from_json`` raise on malformed input — missing or mistyped
    fields, NaN/negative times, type-confused shapes, absurd op counts —
    so obvious poison is rejected at the wire (the front ends map
    ``ValueError`` to a 400) instead of crashing deep inside numpy once
    the engine consumes the arrays.  Valid documents decode exactly as
    before: the bitwise round-trip guarantees below are unchanged."""


#: per kernel-varying kind, the params ``Op.feature_vector`` reads, in
#: order, each with the value it takes when absent (padded to 7 with 0);
#: a present one must be numeric, or MLP scoring would crash
#: mid-engine-pass long after admission
_FEATURE_LAYOUT = {
    "conv2d": (("batch", 1), ("in_ch", 1), ("out_ch", 1), ("kernel", 1),
               ("padding", 0), ("stride", 1), ("image", 1)),
    "linear": (("batch", 1), ("in_f", 1), ("out_f", 1), ("bias", 0)),
    "bmm": (("b", 1), ("m", 1), ("n", 1), ("k", 1)),
    "recurrent": (("batch", 1), ("in_f", 1), ("hidden", 1), ("seq", 1),
                  ("layers", 1), ("bidir", 0), ("bias", 0)),
}

_MAX_OPS_DEFAULT = 500_000


def _trace_max_ops() -> int:
    """``REPRO_TRACE_MAX_OPS`` (default 500000): the wire-entry cap on
    ops per trace.  Parsed leniently (the env-knob policy: malformed
    overrides keep the default) — duplicated from ``core.batched`` 's
    ``env_int`` because importing it here would be a cycle."""
    raw = os.environ.get("REPRO_TRACE_MAX_OPS")
    if raw is None:
        return _MAX_OPS_DEFAULT
    try:
        v = int(raw)
    except ValueError:
        return _MAX_OPS_DEFAULT
    return v if v > 0 else _MAX_OPS_DEFAULT


def _v_str(v: Any, where: str) -> str:
    if not isinstance(v, str):
        raise TraceValidationError(
            f"{where}: expected a string, got {type(v).__name__}")
    return v


def _v_num(v: Any, where: str, allow_none: bool = False,
           integral: bool = False):
    """Validate one numeric field: a real, finite, non-negative number
    (numpy scalars welcome; bools and numeric *strings* are rejected —
    a type-confused field must not silently coerce, or the decode would
    no longer round-trip bitwise)."""
    if v is None and allow_none:
        return None
    if isinstance(v, bool) or not isinstance(v, numbers.Real):
        raise TraceValidationError(
            f"{where}: expected a number, got {type(v).__name__}: {v!r}")
    try:
        f = float(v)
    except OverflowError:       # an integer past float64's range
        raise TraceValidationError(
            f"{where}: too large for a float") from None
    if not math.isfinite(f):
        raise TraceValidationError(f"{where}: must be finite, got {f!r}")
    if f < 0:
        raise TraceValidationError(f"{where}: must be >= 0, got {f!r}")
    if integral:
        if f != int(f):
            raise TraceValidationError(
                f"{where}: must be an integer, got {f!r}")
        return int(f)
    return f


def _v_shapes(v: Any, where: str) -> Tuple[Tuple[int, ...], ...]:
    if not isinstance(v, (list, tuple)):
        raise TraceValidationError(
            f"{where}: expected a list, got {type(v).__name__}")
    out = []
    for i, s in enumerate(v):
        if not isinstance(s, (list, tuple)):
            raise TraceValidationError(
                f"{where}[{i}]: expected a shape list, "
                f"got {type(s).__name__}")
        out.append(tuple(_v_num(x, f"{where}[{i}]", integral=True)
                         for x in s))
    return tuple(out)


def _classify_dot(eqn, cost_params) -> Tuple[str, Dict[str, Any]]:
    b = cost_params.get("b", 1)
    m, n, k = (cost_params.get(x, 1) for x in ("m", "n", "k"))
    if b > 1:
        return "bmm", {"b": b, "m": m, "n": n, "k": k}
    return "linear", {"batch": m, "in_f": k, "out_f": n, "bias": 0,
                      "b": b, "m": m, "n": n, "k": k}


def _classify_conv(eqn) -> Dict[str, Any]:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    ls, rs = dnums.lhs_spec, dnums.rhs_spec
    spatial = [lhs.shape[d] for d in ls[2:]]
    ksize = [rhs.shape[d] for d in rs[2:]]
    strides = eqn.params.get("window_strides", (1,))
    padding = eqn.params.get("padding", ((0, 0),))
    return {
        "batch": lhs.shape[ls[0]], "in_ch": lhs.shape[ls[1]],
        "out_ch": rhs.shape[rs[0]],
        "kernel": ksize[0] if ksize else 1,
        "stride": strides[0] if strides else 1,
        "padding": padding[0][0] if padding else 0,
        "image": spatial[0] if spatial else 1,
    }


def _sub_jaxpr(eqn):
    """The jaxpr a call-like equation runs, or None."""
    sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    if sub is None:
        return None
    return sub.jaxpr if hasattr(sub, "jaxpr") else sub


def _has_matmul(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
            return True
        sub = _sub_jaxpr(eqn)
        if sub is not None and _has_matmul(sub):
            return True
    return False


def _matmul_reads(jaxpr, sliced) -> bool:
    """Whether a matmul of ``jaxpr`` (or of a call inside it) takes one of
    the variables ``sliced`` as an operand, through relabelling ops."""
    sliced = set(sliced)
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [v for v in eqn.invars if not isinstance(v, jcore.Literal)]
        if prim in ("dot_general", "conv_general_dilated"):
            if any(v in sliced for v in ins):
                return True
        elif prim in _RELABEL:
            if ins and ins[0] in sliced:
                sliced.update(eqn.outvars)
        elif (sub := _sub_jaxpr(eqn)) is not None:    # a call, a scan
            if len(sub.invars) == len(eqn.invars) and _matmul_reads(
                    sub, [s for s, v in zip(sub.invars, eqn.invars)
                          if not isinstance(v, jcore.Literal)
                          and v in sliced]):
                return True
    return False


def _scan_by_trip_count(eqn) -> bool:
    """Whether a scan is walked as ``length`` runs of its body rather than
    kept as one ``recurrent`` op.  A scan whose matmuls take a per-step
    slice of its ``xs`` (a layer stack's weights, a chunk's states, a
    block of keys) is a loop over independent work; one whose matmuls
    take only loop-invariant operands and the carry (an LSTM's cell) is
    a recurrence, and so is priced as one."""
    body = eqn.params["jaxpr"].jaxpr
    if not _has_matmul(body):
        return True
    first_xs = eqn.params["num_consts"] + eqn.params["num_carry"]
    return _matmul_reads(body, body.invars[first_xs:])


def _recurrent_params(eqn) -> Dict[str, Any]:
    body = eqn.params["jaxpr"].jaxpr
    length = eqn.params["length"]
    hidden = batch = in_f = 1
    for beqn in body.eqns:
        if beqn.primitive.name == "dot_general":
            _, p = costmodel.eqn_cost(beqn)
            batch = max(batch, p.get("m", 1))
            in_f = max(in_f, p.get("k", 1))
            hidden = max(hidden, p.get("n", 1))
    return {"batch": batch, "in_f": in_f, "hidden": hidden, "seq": length,
            "layers": 1, "bidir": 0, "bias": 0}


def _walk(jaxpr, ops: List[Op], multiplicity: int) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _HIGHER_ORDER:
            sub = _sub_jaxpr(eqn)
            if sub is not None:
                with (telemetry.span("trace.jit_walked")
                      if prim in ("jit", "pjit") else contextlib.nullcontext()):
                    _walk(sub, ops, multiplicity)
            continue
        if prim == "cond":
            # Track the most expensive branch (paper: worst case per step).
            branches = eqn.params["branches"]
            costs = [costmodel.jaxpr_cost(b.jaxpr) for b in branches]
            best = int(np.argmax([c.flops + c.bytes_accessed for c in costs]))
            _walk(branches[best].jaxpr, ops, multiplicity)
            continue
        if prim == "while":
            _walk(eqn.params["body_jaxpr"].jaxpr, ops, multiplicity)
            continue
        if prim == "scan":
            length = int(eqn.params["length"])
            if _scan_by_trip_count(eqn):
                with telemetry.span("trace.scan_walked"):
                    _walk(eqn.params["jaxpr"].jaxpr, ops,
                          multiplicity * length)
                continue
            with telemetry.span("trace.scan_recurrent"):
                cost, _ = costmodel.eqn_cost(eqn)
                ops.append(Op(
                    name="scan", kind="recurrent", cost=cost,
                    multiplicity=multiplicity, params=_recurrent_params(eqn),
                    in_shapes=tuple(tuple(v.aval.shape) for v in eqn.invars
                                    if hasattr(v, "aval")),
                    out_shapes=tuple(tuple(v.aval.shape)
                                     for v in eqn.outvars),
                    dtype=_dtype_of(eqn)))
            continue

        if prim == "pallas_call" or _sub_jaxpr(eqn) is not None:
            # a call this walk does not enter: priced as one elementwise op
            with telemetry.span("trace.priced_default"):
                _append_op(eqn, prim, ops, multiplicity)
        else:
            _append_op(eqn, prim, ops, multiplicity)


def _append_op(eqn, prim: str, ops: List[Op], multiplicity: int) -> None:
    cost, cparams = costmodel.eqn_cost(eqn)
    if prim == "dot_general":
        kind, params = _classify_dot(eqn, cparams)
    elif prim == "conv_general_dilated":
        kind, params = "conv2d", _classify_conv(eqn)
    else:
        kind, params = prim, dict(cparams)
    ops.append(Op(
        name=prim, kind=kind, cost=cost, multiplicity=multiplicity,
        params=params,
        in_shapes=tuple(tuple(v.aval.shape) for v in eqn.invars
                        if hasattr(v, "aval")
                        and not isinstance(v, jcore.Literal)),
        out_shapes=tuple(tuple(v.aval.shape) for v in eqn.outvars),
        dtype=_dtype_of(eqn)))


def _dtype_of(eqn) -> str:
    for v in eqn.outvars:
        if hasattr(v, "aval") and hasattr(v.aval, "dtype"):
            return str(v.aval.dtype)
    return "float32"


@dataclasses.dataclass
class TraceArrays:
    """Structure-of-arrays view of a trace (one row per op).

    This is the input format of the vectorized fleet-prediction engine
    (``core/batched.py``): all per-op scalars are pulled out of the ``Op``
    objects once, so predicting against N destination devices is pure
    array math instead of N Python loops over the op list.

    ``measured_ms`` is NaN for ops without an origin measurement;
    ``kind_ids[i]`` indexes into ``kinds``; ``op_features`` are the *raw*
    (un-log-transformed) 9-dim MLP op features of :meth:`Op.feature_vector`.
    """
    flops: np.ndarray            # (n_ops,)
    bytes_accessed: np.ndarray   # (n_ops,)
    intensity: np.ndarray        # (n_ops,)
    measured_ms: np.ndarray      # (n_ops,) NaN where unmeasured
    multiplicity: np.ndarray     # (n_ops,)
    kernel_varying: np.ndarray   # (n_ops,) bool
    kind_ids: np.ndarray         # (n_ops,) int32 index into ``kinds``
    kinds: List[str]             # unique kinds, sorted
    op_features: np.ndarray      # (n_ops, 9) raw MLP op features
    _fingerprint: Optional[str] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_ops(self) -> int:
        return int(self.flops.shape[0])

    def fingerprint(self) -> str:
        """Stable content hash, used as a result-cache key.

        Memoized: the serving path fingerprints every trace of every
        query (cache keys, sweep dedup), and the arrays are treated as
        immutable once built."""
        if self._fingerprint is None:
            h = hashlib.sha1()
            for arr in (self.flops, self.bytes_accessed, self.measured_ms,
                        self.multiplicity, self.kind_ids, self.op_features):
                h.update(np.ascontiguousarray(arr).tobytes())
            h.update("|".join(self.kinds).encode())
            self._fingerprint = h.hexdigest()
        return self._fingerprint


class _LazyOps:
    """``TrackedTrace.ops``: a trace decoded column by column keeps its
    document's op list and builds its ``Op`` objects on the first read
    (span ``trace.ops_built``); a trace built from an ``Op`` list holds
    it as given."""

    def __get__(self, obj, owner=None) -> List[Op]:
        if obj is None:         # class access: the field has no default
            raise AttributeError("ops")
        ops = obj.__dict__["_ops"]
        if ops is None:
            with telemetry.span("trace.ops_built"):
                ops = _ops_from_doc(obj._doc)
            obj.__dict__["_ops"] = ops
            obj._doc = None
        return ops

    def __set__(self, obj, ops: List[Op]) -> None:
        obj.__dict__["_ops"] = ops
        obj.__dict__["_doc"] = None     # these ops replace the document's


@dataclasses.dataclass
class TrackedTrace:
    """The result of tracking one training/serving iteration."""
    ops: List[Op] = _LazyOps()
    origin_device: str
    label: str = "iteration"
    #: fraction of iteration time that :meth:`measure` timed for real on
    #: the origin device (the rest was simulated); not part of the wire
    #: format or the fingerprint
    coverage: Optional[float] = dataclasses.field(
        default=None, repr=False, compare=False)
    _arrays: Optional[TraceArrays] = dataclasses.field(
        default=None, repr=False, compare=False)
    _fp: Optional[str] = dataclasses.field(
        default=None, repr=False, compare=False)
    #: the op documents ``ops`` is built from on first read (column-wise
    #: decode only; None once built)
    _doc: Optional[List[Dict[str, Any]]] = dataclasses.field(
        default=None, repr=False, compare=False)
    #: ``run_time_ms`` as the column-wise decode summed it (None where an
    #: op is unmeasured), read while ``_doc`` stands for the ops
    _run_ms: Optional[float] = dataclasses.field(
        default=None, repr=False, compare=False)

    # ---- aggregate views -------------------------------------------------
    @property
    def run_time_ms(self) -> float:
        if self._doc is not None:       # ops not built yet: the decode's
            total = self._run_ms
        else:
            times = [(op.predicted_ms if op.predicted_ms is not None
                      else op.measured_ms) for op in self.ops]
            total = None if any(t is None for t in times) else float(
                sum(t * op.multiplicity for t, op in zip(times, self.ops)))
        if total is None:
            raise ValueError("trace has unmeasured ops; call measure() first")
        return total

    @property
    def total_cost(self) -> OpCost:
        total = OpCost()
        for op in self.ops:
            total = total + op.cost.scaled(op.multiplicity)
        return total

    def breakdown(self) -> Dict[str, float]:
        """Per-kind time breakdown in ms (paper Fig. 4)."""
        out: Dict[str, float] = {}
        for op in self.ops:
            t = op.predicted_ms if op.predicted_ms is not None \
                else (op.measured_ms or 0.0)
            out[op.kind] = out.get(op.kind, 0.0) + t * op.multiplicity
        return out

    def to_arrays(self, refresh: bool = False) -> TraceArrays:
        """Structure-of-arrays export for the vectorized prediction engine.

        The result is cached on the trace (per-op Python extraction is the
        last scalar loop on the fleet path); :meth:`measure` invalidates it.
        Pass ``refresh=True`` after mutating ops by hand."""
        if self._arrays is not None and not refresh:
            return self._arrays
        self._fp = None                 # fingerprint follows the arrays
        n = len(self.ops)
        kinds = sorted({op.kind for op in self.ops})
        kind_index = {k: i for i, k in enumerate(kinds)}
        flops = np.empty(n, np.float64)
        bytes_accessed = np.empty(n, np.float64)
        intensity = np.empty(n, np.float64)
        measured = np.full(n, np.nan, np.float64)
        mult = np.empty(n, np.float64)
        varying = np.zeros(n, bool)
        kind_ids = np.empty(n, np.int32)
        feats = np.zeros((n, 9), np.float64)
        for i, op in enumerate(self.ops):
            flops[i] = op.cost.flops
            bytes_accessed[i] = op.cost.bytes_accessed
            intensity[i] = op.cost.intensity
            if op.measured_ms is not None:
                measured[i] = op.measured_ms
            mult[i] = op.multiplicity
            varying[i] = op.kernel_varying
            kind_ids[i] = kind_index[op.kind]
            feats[i] = op.feature_vector()
        self._arrays = TraceArrays(
            flops=flops, bytes_accessed=bytes_accessed, intensity=intensity,
            measured_ms=measured, multiplicity=mult, kernel_varying=varying,
            kind_ids=kind_ids, kinds=kinds, op_features=feats)
        return self._arrays

    def fingerprint(self) -> str:
        """Content hash of the trace (ops + origin), for result caches.

        Memoized alongside the SoA cache (``to_arrays``); invalidated by
        :meth:`measure` and by ``to_arrays(refresh=True)``."""
        if self._fp is None:
            h = hashlib.sha1(self.to_arrays().fingerprint().encode())
            h.update(self.origin_device.encode())
            self._fp = h.hexdigest()
        return self._fp

    # ---- serialization ---------------------------------------------------
    # Wire-format guarantees (the prediction service ships traces as
    # these documents): from_json(to_json(t)) reproduces t's fingerprint,
    # run_time_ms, and every prediction BITWISE — Python floats survive
    # json round-trips exactly (shortest-repr), and to_dict coerces all
    # numerics to native Python numbers.  to_dict(from_dict(d)) == d, so
    # re-serialization is idempotent.  Pinned by tests/test_trace_wire.py.
    def to_dict(self) -> Dict[str, Any]:
        """JSON-safe record: the golden-trace on-disk and service wire
        format (see the round-trip guarantees above)."""
        return {"origin_device": self.origin_device, "label": self.label,
                "ops": [op.to_dict() for op in self.ops]}

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "TrackedTrace":
        """Decode a trace document, validating every field.

        Raises :class:`TraceValidationError` (a ``ValueError``; front
        ends answer 400) on malformed input: wrong container types,
        mistyped fields, NaN/negative times, op counts over
        ``REPRO_TRACE_MAX_OPS``.  The origin device is deliberately NOT
        checked against the registry here — an unknown origin is a
        semantic failure the engine reports (and the quarantine layer
        tracks), not a malformed document.

        A sound document decodes column by column straight into the
        trace's arrays and fingerprint; its ``ops`` are built on first
        read, and the trace keeps ``d["ops"]`` until then, so the caller
        leaves it unchanged.  Anything else (an error, NumPy scalars,
        integers of 2**53 or more) takes the per-op decode (span
        ``trace.decode_slow``); both give the same trace."""
        trace = _decode_columns(d)
        if trace is None:
            with telemetry.span("trace.decode_slow"):
                trace = _decode_per_op(d)
        return trace

    def to_json(self) -> str:
        import json
        return json.dumps(self.to_dict(), indent=1)

    @staticmethod
    def from_json(text: str) -> "TrackedTrace":
        import json
        try:
            doc = json.loads(text)
        except json.JSONDecodeError as e:
            raise TraceValidationError(
                f"trace document is not valid JSON: {e}") from None
        return TrackedTrace.from_dict(doc)

    def measure(self, method: str = "simulate") -> "TrackedTrace":
        """Fill ``measured_ms`` for every op on the origin device:
        ``"simulate"`` prices each op with the simulator, ``"wallclock"``
        times it on the device this process runs on, which must be the
        origin (see :func:`repro.core.calibration.measure_trace_inplace`)."""
        self._arrays = None  # measured_ms changes under the SoA cache
        self._fp = None
        if method == "simulate":
            from repro.core import simulator
            dev = devices.get(self.origin_device)
            for op in self.ops:
                op.measured_ms = simulator.op_time_ms(op, dev)
            self.coverage = 0.0
        elif method == "wallclock":
            from repro.core import calibration
            self.coverage = calibration.measure_trace_inplace(self)
        else:
            raise ValueError(f"unknown measure method {method!r}")
        return self

    def to_device(self, dest: str, predictor=None) -> "TrackedTrace":
        """Predict this trace's execution on a different device (Listing 1)."""
        from repro.core import predictor as predictor_mod
        pred = predictor or predictor_mod.default_predictor()
        return pred.predict_trace(self, dest)


def _decode_per_op(d: Any) -> TrackedTrace:
    """Decode a trace document op by op through :meth:`Op.from_dict`."""
    if not isinstance(d, dict):
        raise TraceValidationError(
            f"trace document must be an object, "
            f"got {type(d).__name__}")
    try:
        ops_doc, origin = d["ops"], d["origin_device"]
    except KeyError as e:
        raise TraceValidationError(
            f"trace document missing field {e}") from None
    if not isinstance(ops_doc, list):
        raise TraceValidationError(
            f"trace.ops must be a list, got {type(ops_doc).__name__}")
    max_ops = _trace_max_ops()
    if len(ops_doc) > max_ops:
        raise TraceValidationError(
            f"trace has {len(ops_doc)} ops, over the wire-entry cap "
            f"of {max_ops} (REPRO_TRACE_MAX_OPS)")
    origin = _v_str(origin, "trace.origin_device")
    label = _v_str(d.get("label", "iteration"), "trace.label")
    return TrackedTrace(ops=[Op.from_dict(o) for o in ops_doc],
                        origin_device=origin, label=label)


#: ints below this read the same as ints and as float64 (an int at or
#: above it may read as another, after the float64 rounds it)
_EXACT_INT = 2 ** 53
_NUMBER = {float, int}
_NUMBER_OR_NONE = {float, int, type(None)}


def _of_types(col: List[Any], types) -> bool:
    return set(map(type, col)) <= types


def _num_column(col: List[Any], allow_none: bool = False,
                integral: bool = False) -> Optional[np.ndarray]:
    """A column of ``_v_num`` fields as float64 (NaN where None), or None
    where the per-op check could reject it or read it otherwise: a type
    other than ``float``, ``int`` (and None where ``allow_none``), a NaN,
    infinite or negative value, a fraction where ``integral``, an
    ``int`` of 2**53 or more."""
    types = set(map(type, col))
    if not types <= (_NUMBER_OR_NONE if allow_none else _NUMBER):
        return None
    arr = np.array(col, np.float64)
    vals = arr
    if type(None) in types:
        missing = np.isnan(arr)
        if np.count_nonzero(missing) != col.count(None):
            return None         # a NaN in the document, not a None
        arr[missing] = np.nan
        vals = arr[~missing]
    if not ((vals >= 0) & (vals < np.inf)).all():
        return None
    if int in types and (vals >= _EXACT_INT).any():
        return None
    if integral:
        if (vals != np.floor(vals)).any():
            return None
        arr += 0.0              # -0.0 reads as int 0 in the per-op decode
    return arr


def _shapes_column(col: List[Any]) -> bool:
    """Whether every entry is a list of lists of ints in [0, 2**53)."""
    if not _of_types(col, {list}):
        return False
    shapes = [s for v in col for s in v]
    if not _of_types(shapes, {list}):
        return False
    dims = [x for s in shapes for x in s]
    return _of_types(dims, {int}) and (
        not dims or (min(dims) >= 0 and max(dims) < _EXACT_INT))


def _decode_columns(d: Any) -> Optional[TrackedTrace]:
    """A trace document decoded column by column into the trace's
    arrays and fingerprint, with its ops left to build on demand; None
    where anything in it is not what a sound JSON document holds, so
    that the per-op decode reads it (and raises if it is malformed).

    Each column is pulled out whole, type-checked once and checked with
    NumPy to the per-op rules; the arithmetic is ``to_arrays``' and
    ``Op.feature_vector``'s in float64, so the arrays are bitwise
    theirs."""
    if type(d) is not dict:
        return None
    ops_doc, origin = d.get("ops"), d.get("origin_device")
    label = d.get("label", "iteration")
    if (type(ops_doc) is not list or type(origin) is not str
            or type(label) is not str or len(ops_doc) > _trace_max_ops()
            or not _of_types(ops_doc, {dict})):
        return None
    try:
        kind_col = [o["kind"] for o in ops_doc]
        if not (_of_types(kind_col, {str})
                and _of_types([o["name"] for o in ops_doc], {str})
                and _of_types([o["dtype"] for o in ops_doc], {str})
                and _shapes_column([o["in_shapes"] for o in ops_doc])
                and _shapes_column([o["out_shapes"] for o in ops_doc])):
            return None
        costs = [o["cost"] for o in ops_doc]
        params = [o["params"] for o in ops_doc]
        if not (_of_types(costs, {dict}) and _of_types(params, {dict})):
            return None
        flops = _num_column([c["flops"] for c in costs])
        read = _num_column([c["bytes_read"] for c in costs])
        written = _num_column([c["bytes_written"] for c in costs])
        mult = _num_column([o["multiplicity"] for o in ops_doc],
                           integral=True)
        measured = _num_column([o["measured_ms"] for o in ops_doc],
                               allow_none=True)
        predicted = _num_column([o["predicted_ms"] for o in ops_doc],
                                allow_none=True)
        if any(c is None for c in (flops, read, written, mult, measured,
                                   predicted)):
            return None
        kinds = sorted(set(kind_col))
        kind_index = {k: i for i, k in enumerate(kinds)}
        kind_ids = np.fromiter(map(kind_index.__getitem__, kind_col),
                               np.int32, len(kind_col))
        varying = np.array([k in KERNEL_VARYING_KINDS for k in kinds],
                           bool)[kind_ids]
        bytes_accessed = read + written
        intensity = flops / np.maximum(bytes_accessed, 1.0)
        feats = np.zeros((len(ops_doc), 9), np.float64)
        feats[:, 0] = intensity         # kernel-varying rows: below
        feats[:, 7] = flops
        feats[:, 8] = bytes_accessed
        for kind, layout in _FEATURE_LAYOUT.items():
            if kind not in kind_index:
                continue
            rows = np.flatnonzero(kind_ids == kind_index[kind])
            kind_params = [params[i] for i in rows.tolist()]
            for j, (key, default) in enumerate(layout):
                col = _num_column([p.get(key, default)
                                   for p in kind_params])
                if col is None:
                    return None
                feats[rows, j] = col
    except (KeyError, OverflowError):   # a missing field; an int past 2**63
        return None
    times = np.where(np.isnan(predicted), measured, predicted)
    run_ms = None if np.isnan(times).any() else float(
        sum((times * mult).tolist()))   # run_time_ms's sum, term by term
    trace = TrackedTrace(
        ops=None, origin_device=origin, label=label, _doc=ops_doc,
        _run_ms=run_ms,
        _arrays=TraceArrays(
            flops=flops, bytes_accessed=bytes_accessed, intensity=intensity,
            measured_ms=measured, multiplicity=mult, kernel_varying=varying,
            kind_ids=kind_ids, kinds=kinds, op_features=feats))
    trace.fingerprint()
    return trace


def _ops_from_doc(ops_doc: List[Dict[str, Any]]) -> List[Op]:
    """The ops of a document :func:`_decode_columns` accepted, checked
    no further: the ``Op`` objects ``Op.from_dict`` builds from it."""
    ops = []
    for o in ops_doc:
        c, m, p = o["cost"], o["measured_ms"], o["predicted_ms"]
        ops.append(Op(
            name=o["name"], kind=o["kind"],
            cost=OpCost(flops=float(c["flops"]),
                        bytes_read=float(c["bytes_read"]),
                        bytes_written=float(c["bytes_written"])),
            multiplicity=int(o["multiplicity"]),
            params=dict(o["params"]),
            in_shapes=tuple(map(tuple, o["in_shapes"])),
            out_shapes=tuple(map(tuple, o["out_shapes"])),
            dtype=o["dtype"],
            measured_ms=None if m is None else float(m),
            predicted_ms=None if p is None else float(p)))
    return ops


class OperationTracker:
    """Traces a step function and measures per-op times on the origin."""

    def __init__(self, origin_device: str = "cpu-host",
                 measure: str = "simulate"):
        self.origin_device = origin_device
        self.measure_method = measure

    def track(self, fn, *args, label: str = "iteration",
              **kwargs) -> TrackedTrace:
        with telemetry.span("trace.track"):
            closed = jax.make_jaxpr(fn)(*args, **kwargs)
            ops: List[Op] = []
            _walk(closed.jaxpr, ops, 1)
        trace = TrackedTrace(ops=ops, origin_device=self.origin_device,
                             label=label)
        return trace.measure(self.measure_method)
