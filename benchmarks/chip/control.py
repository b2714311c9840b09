"""The control of the serving cells' ``answer_gap``: the reference put in
the program's place, one precision below what the configuration states.

    python3 benchmarks/chip/control.py --workload resnet50.rank-cold \\
        --seeds 11,12,13

The MLPs are float32 at ``Precision.HIGHEST``; the step below is
``HIGH``, three bfloat16 passes (each operand split into a bfloat16 high
part and a bfloat16 low part, the low-by-low product dropped), written out
so that it is the same computation on any backend.  For each seed it
scores every kernel-varying op of the cell's corpus on every device with
that forward, on the chip, and prints the ``answer_gap`` the serving
check would read: the widest gap to the float64 reference as a share of
the reference's MLP-priced part, and the same with the products at
``Precision.HIGH`` as the compiler does them (three passes on a TPU, full
float32 on the CPU).  The benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)

import numpy as np  # noqa: E402

from benchmarks.chip import bench, reference  # noqa: E402


def _dot_bf16x3(a, b):
    """``a @ b`` as three bfloat16 passes: each operand's bfloat16 part and
    the bfloat16 part of its remainder, the low-by-low product left out.
    ``reduce_precision`` keeps the parts float32 values that XLA may not
    widen back, and products of bfloat16 values are exact at HIGHEST."""
    import jax
    import jax.numpy as jnp

    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)
    dot = lambda x, y: jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _dot_high(a, b):
    """``a @ b`` at ``Precision.HIGH``: on a TPU, three bfloat16 passes by
    the compiler itself (the CPU computes it in full float32)."""
    import jax
    import jax.numpy as jnp

    return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)


@functools.lru_cache(maxsize=None)
def _forward(dot):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, ws, bs):
        h = x
        for i, (w, b) in enumerate(zip(ws, bs)):
            h = dot(h, w) + b
            if i < len(ws) - 1:
                h = jnp.maximum(h, 0.0)
        return h[:, 0]
    return f


def forward(m: dict, x: np.ndarray, dot=_dot_bf16x3) -> np.ndarray:
    """log(ms) of standardised rows ``x``: float32 layers whose products
    are ``dot``."""
    import jax.numpy as jnp

    out = _forward(dot)(jnp.asarray(x, jnp.float32),
                        [jnp.asarray(w) for w in m["w"]],
                        [jnp.asarray(b) for b in m["b"]])
    return np.asarray(out, np.float64)


def control_gap(docs, mlps, devs, dot=_dot_bf16x3) -> float:
    """The widest gap of the control's MLP part to the reference's, as a
    share of the reference's, over every document and device."""
    fwd = functools.partial(forward, dot=dot)
    gap = 0.0
    for doc in docs:
        ref = reference.mlp_part(doc, devs, mlps)
        ctl = reference.mlp_part(doc, devs, mlps, forward=fwd)
        gap = max(gap, float(np.max(np.abs(ctl - ref) / ref)))
    return gap


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--any-platform", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.any_platform:
        raise SystemExit(f"control: JAX runs on {dev.platform!r}, not a TPU")
    cell = bench.cell(bench.load_benchmark(), args.workload)
    docs = [reference.Doc(d["doc"])
            for d in bench.corpus(bench.load_config(cell["config"]))]
    devs = reference.device_table()
    stats = reference.feature_stats(docs, devs)
    for seed in args.seeds:
        mlps = reference.make_mlps(seed, stats)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_answer_gap": control_gap(docs, mlps, devs),
            "lax_high_answer_gap": control_gap(docs, mlps, devs, _dot_high),
            "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    main()
