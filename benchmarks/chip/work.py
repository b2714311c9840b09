"""Operations and bytes the MLP scorer's work needs, counted from the
MLPs' own layer shapes: no padding rows, no blocks of other kinds.

A row is one (op, device) cell of a kernel-varying op: ``N_FEATURES``
inputs through the hidden layers to one output.
"""

from __future__ import annotations

from typing import Sequence

from benchmarks.chip import reference

BYTES = 4                       # float32 rows, weights and outputs
#: stable names (``profile.op_name``) of the scorer's Pallas kernels
SCORER = r"^fused_mlp_score"


def layer_shapes(n_in: int = reference.N_FEATURES,
                 hidden: int = reference.HIDDEN,
                 layers: int = reference.HIDDEN_LAYERS):
    sizes = [n_in] + [hidden] * layers + [1]
    return list(zip(sizes[:-1], sizes[1:]))


def flops_per_row(shapes=None) -> float:
    """2 x (multiply-adds) of one row's forward pass."""
    return float(sum(2 * a * b for a, b in (shapes or layer_shapes())))


def weight_bytes(shapes=None) -> float:
    return float(sum((a * b + b) * BYTES for a, b in (shapes or layer_shapes())))


def mlp_work(rows: int, launches: int, kinds_per_launch: float,
             shapes=None) -> tuple:
    """(flops, bytes) of scoring ``rows`` rows in ``launches`` launches,
    each reading the weights of ``kinds_per_launch`` MLPs once and each
    row's inputs and output once."""
    shapes = shapes or layer_shapes()
    flops = rows * flops_per_row(shapes)
    row_bytes = rows * (shapes[0][0] + shapes[-1][1]) * BYTES
    return flops, row_bytes + launches * kinds_per_launch * weight_bytes(shapes)


def cold_rows(ctx) -> tuple:
    """(rows, kinds) the window's answered requests needed the scorer for:
    every kernel-varying op of a trace on every device, times the share
    of (trace, device) cells the result cache missed."""
    rows, kinds = 0, set()
    n_dev = len(ctx["devs"])
    for r, o in zip(ctx["requests"], ctx["outcomes"]):
        if o is None or o.status != 200:
            continue
        for i, _ in r.traces:
            doc = ctx["rdocs"][i]
            rows += doc.n_varying * n_dev
            kinds.update(k for k, v in doc.mlp_rows.items() if v)
    a, b = ctx["stats_before"]["cache"], ctx["stats_after"]["cache"]
    hits, misses = a_b(a, b, "hits"), a_b(a, b, "misses")
    cold = misses / (hits + misses) if hits + misses else 0.0
    return rows * cold, kinds


def a_b(before: dict, after: dict, key: str) -> float:
    return float(after[key] - before[key])


def scorer_launches(prof: dict, pattern: str) -> int:
    import re
    rx = re.compile(pattern)
    return sum(n for name, n in prof["op_n"].items() if rx.search(name))


def shares(ctx, pattern: str = SCORER) -> Sequence[float]:
    """(roofline %, engine MFU %) of the window's scorer work."""
    rows, kinds = cold_rows(ctx)
    prof, peak = ctx["profile"], ctx["peaks"]
    launches = scorer_launches(prof, pattern)
    from benchmarks.chip import profile
    kernel_s = profile.kernel_seconds(prof, pattern)
    if peak is None or not rows or not launches or kernel_s <= 0:
        return None, None
    flops, nbytes = mlp_work(int(rows), launches, len(kinds))
    least_s = max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return (100.0 * least_s / kernel_s,
            100.0 * flops / ctx["window_s"] / peak["flops_per_s"])
