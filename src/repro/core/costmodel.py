"""Analytical per-primitive cost model over jaxpr equations.

This is the TPU-side replacement for the CUPTI-measured metrics Habitat
gathers on GPUs (Sec. 4.2): for every jaxpr equation we compute

  * ``flops``          -- floating point operations
  * ``bytes_accessed`` -- bytes read from + written to HBM (assuming no fusion)
  * arithmetic intensity = flops / bytes_accessed

which feed (i) the roofline-based γ selection (Eq. 3), (ii) the device
simulator, and (iii) the §Roofline deliverable.

The model intentionally over-counts memory traffic relative to a fusing
compiler (each op reads its inputs and writes its output) — this mirrors the
paper's kernel-level view, where every CUDA kernel really does round-trip
through DRAM.
"""

from __future__ import annotations

import dataclasses
import math
from functools import reduce
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jcore


def _size(aval) -> int:
    return int(np.prod(aval.shape)) if aval.shape else 1


def _bytes(aval) -> int:
    dtype = getattr(aval, "dtype", None)
    itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
    return _size(aval) * itemsize


@dataclasses.dataclass
class OpCost:
    flops: float = 0.0
    bytes_read: float = 0.0
    bytes_written: float = 0.0

    @property
    def bytes_accessed(self) -> float:
        return self.bytes_read + self.bytes_written

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs/byte); paper Fig. 2's x-axis."""
        return self.flops / max(self.bytes_accessed, 1.0)

    def __add__(self, other: "OpCost") -> "OpCost":
        return OpCost(self.flops + other.flops,
                      self.bytes_read + other.bytes_read,
                      self.bytes_written + other.bytes_written)

    def scaled(self, k: float) -> "OpCost":
        return OpCost(self.flops * k, self.bytes_read * k,
                      self.bytes_written * k)


# FLOPs-per-element for elementwise primitives that are more expensive than
# one op.  Everything else defaults to 1 flop/element.
_ELEMENTWISE_WEIGHT = {
    "exp": 4, "log": 4, "log1p": 4, "expm1": 4,
    "sin": 4, "cos": 4, "tan": 6, "tanh": 6, "logistic": 6,
    "erf": 8, "erf_inv": 8, "erfc": 8,
    "rsqrt": 2, "sqrt": 2, "cbrt": 4,
    "div": 2, "rem": 2, "pow": 8, "integer_pow": 2,
    "atan2": 10, "sigmoid": 6,
}

# Primitives that are pure data movement (no flops, bytes only).
_MOVEMENT = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "rev",
    "concatenate", "slice", "dynamic_slice", "dynamic_update_slice",
    "pad", "gather", "scatter", "convert_element_type", "bitcast_convert_type",
    "copy", "device_put", "split", "expand_dims", "real", "imag", "iota",
    "select_n", "stop_gradient", "squeeze", "rng_bit_generator",
}

# Collective primitives: tracked separately so the distributed predictor and
# the roofline collective term can see them.
_COLLECTIVES = {
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "pgather", "axis_index",
}


def _dot_general_cost(eqn) -> Tuple[OpCost, Dict[str, int]]:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    (lhs_c, rhs_c), (lhs_b, rhs_b) = dnums
    batch = reduce(int.__mul__, (lhs.shape[d] for d in lhs_b), 1)
    contract = reduce(int.__mul__, (lhs.shape[d] for d in lhs_c), 1)
    m = _size(lhs) // max(batch * contract, 1)
    n = _size(rhs) // max(batch * contract, 1)
    flops = 2.0 * batch * m * n * contract
    cost = OpCost(flops,
                  _bytes(lhs) + _bytes(rhs),
                  sum(_bytes(v.aval) for v in eqn.outvars))
    params = {"b": batch, "m": m, "n": n, "k": contract}
    return cost, params


def _conv_cost(eqn) -> Tuple[OpCost, Dict[str, int]]:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    # flops = 2 * output_size * (reduction per output element)
    dnums = eqn.params["dimension_numbers"]
    rhs_shape = rhs.shape
    # kernel spatial dims * input features per group
    feature_group_count = eqn.params.get("feature_group_count", 1)
    red = _size(rhs) // max(rhs_shape[dnums.rhs_spec[0]], 1)  # per out-channel
    flops = 2.0 * _size(out) * red / max(feature_group_count, 1)
    cost = OpCost(flops, _bytes(lhs) + _bytes(rhs), _bytes(out))
    params = {"out_size": _size(out), "red": red}
    return cost, params


def eqn_cost(eqn) -> Tuple[OpCost, Dict[str, Any]]:
    """Cost of a single jaxpr equation (recursing into sub-jaxprs)."""
    prim = eqn.primitive.name
    params: Dict[str, Any] = {}

    if prim == "dot_general":
        return _dot_general_cost(eqn)
    if prim == "conv_general_dilated":
        return _conv_cost(eqn)

    # Recurse into higher-order primitives.
    if prim == "scan":
        body = eqn.params["jaxpr"]
        length = eqn.params["length"]
        inner = jaxpr_cost(body.jaxpr)
        return inner.scaled(length), {"length": length}
    if prim == "while":
        # Trip count is unknowable statically; assume one iteration of the
        # body (callers that care pass trip-count hints via trace.py).
        inner = jaxpr_cost(eqn.params["body_jaxpr"].jaxpr)
        return inner, {"assumed_trips": 1}
    if prim == "cond":
        branches = eqn.params["branches"]
        costs = [jaxpr_cost(b.jaxpr) for b in branches]
        worst = max(costs, key=lambda c: c.flops + c.bytes_accessed)
        return worst, {"branches": len(branches)}
    if prim in ("jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
                "custom_vjp_call", "custom_vjp_call_jaxpr", "remat_call",
                "remat", "remat2", "checkpoint", "named_call", "custom_lin"):
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None:
            inner_jaxpr = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            return jaxpr_cost(inner_jaxpr), {}
        return OpCost(), {}

    in_bytes = sum(_bytes(v.aval) for v in eqn.invars
                   if hasattr(v, "aval") and not isinstance(v, jcore.Literal))
    out_bytes = sum(_bytes(v.aval) for v in eqn.outvars)

    if prim in _COLLECTIVES:
        # Collective cost: bytes moved over links == operand bytes.
        return OpCost(0.0, in_bytes, out_bytes), {"collective": True}

    if prim in _MOVEMENT:
        return OpCost(0.0, in_bytes, out_bytes), {}

    if prim.startswith("reduce_") or prim in ("argmax", "argmin",
                                              "reduce_precision"):
        flops = float(sum(_size(v.aval) for v in eqn.invars
                          if hasattr(v, "aval")))
        return OpCost(flops, in_bytes, out_bytes), {}
    if prim in ("cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"):
        flops = float(sum(_size(v.aval) for v in eqn.invars
                          if hasattr(v, "aval")))
        return OpCost(flops, in_bytes, out_bytes), {}
    if prim == "sort":
        n = max((_size(v.aval) for v in eqn.invars if hasattr(v, "aval")),
                default=1)
        flops = float(n) * max(math.log2(max(n, 2)), 1.0)
        return OpCost(flops, in_bytes, out_bytes), {}
    if prim == "top_k":
        n = _size(eqn.invars[0].aval)
        flops = float(n) * max(math.log2(max(eqn.params.get("k", 1), 2)), 1.0)
        return OpCost(flops, in_bytes, out_bytes), {}

    # Default: elementwise with a per-primitive weight.
    weight = _ELEMENTWISE_WEIGHT.get(prim, 1)
    out_size = sum(_size(v.aval) for v in eqn.outvars)
    return OpCost(float(weight * out_size), in_bytes, out_bytes), {}


def jaxpr_cost(jaxpr) -> OpCost:
    """Total cost of a (possibly nested) jaxpr."""
    total = OpCost()
    for eqn in jaxpr.eqns:
        c, _ = eqn_cost(eqn)
        total = total + c
    return total


def fn_cost(fn, *args, **kwargs) -> OpCost:
    """Cost of calling ``fn(*args, **kwargs)`` (traced, never executed)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return jaxpr_cost(closed.jaxpr)
