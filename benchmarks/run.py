"""Benchmark harness: one module per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run [--only fig3,roofline] [--smoke]

``--smoke`` runs the CI-sized subset (fleet engine + kernels) with each
bench's reduced problem size — the fast regression gate wired into
``.github/workflows/ci.yml``.

Prints a human-readable report per benchmark, then a final
``name,us_per_call,derived`` CSV block.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from benchmarks.common import Csv  # noqa: E402
from repro import runtime  # noqa: E402

BENCHES = [
    ("table1", "benchmarks.bench_table1_datasets",
     "Table 1: MLP training datasets"),
    ("fig1", "benchmarks.bench_fig1_heuristic",
     "Fig 1: peak-FLOPS heuristic vs Habitat (DCGAN from T4)"),
    ("fig3", "benchmarks.bench_fig3_end_to_end",
     "Fig 3: end-to-end prediction error, 30 GPU pairs x 5 models"),
    ("fig4", "benchmarks.bench_fig4_breakdown",
     "Fig 4: per-operation error breakdown + importance"),
    ("fig5", "benchmarks.bench_fig5_mlp_sensitivity",
     "Fig 5: MLP depth/width sensitivity"),
    ("case_studies", "benchmarks.bench_case_studies",
     "Sec 5.3: cost-efficiency case studies"),
    ("kernels", "benchmarks.bench_kernels",
     "Pallas kernel microbenches (jnp oracle timings)"),
    ("roofline", "benchmarks.bench_roofline",
     "§Roofline: dry-run roofline table (deliverable g)"),
    ("extensions", "benchmarks.bench_extensions",
     "Sec 6 extensions: distributed / mixed precision / batch extrap"),
    ("variants", "benchmarks.bench_variants",
     "Predictor-variant ablation: Eq.2 vs Eq.1 vs overhead modelling"),
    ("fleet", "benchmarks.bench_fleet",
     "Fleet engine: vectorized vs scalar prediction loop (>=10x gate)"),
    ("sweep", "benchmarks.bench_sweep",
     "Multi-trace ragged sweep vs per-trace fleet loop (>=3x gate)"),
    ("service", "benchmarks.bench_service",
     "Coalescing prediction service vs per-request loop (>=3x gate)"),
    ("union", "benchmarks.bench_union",
     "Union-grid coalescing (>=3x) + cell-masked warm sweeps (>=2x)"),
    ("dispatch", "benchmarks.bench_dispatch",
     "Single-dispatch hot path: row-mapped scorer (>=2x, 1 dispatch) + "
     "warm wave factor (>=3x) + union/split planner (never slower)"),
    ("frontdoor", "benchmarks.bench_frontdoor",
     "Async front door: open-loop overload gate (sheds at 2x, goodput "
     ">=80%, p99 bounded) + threaded baseline"),
    ("cluster", "benchmarks.bench_cluster",
     "Cross-host tier: 3 workers + netcache, no shared fs (>=50% "
     "cross-worker hits, bitwise answers, lossless worker-kill failover)"),
    ("optimizer", "benchmarks.bench_optimizer",
     "What-if optimizer: generation-batched Pareto search (>=5x vs "
     "naive per-candidate loop, passes <= generations, bitwise parity)"),
    ("chaos", "benchmarks.bench_chaos",
     "Fault-tolerant serving: deadlines honored under 10x injected "
     "slowness (>=95% within deadline+100ms), supervised SIGKILL restart "
     "(zero lost, re-admitted <=3 sweeps), fault parity (bitwise)"),
    ("recovery", "benchmarks.bench_recovery",
     "Durable warm state: post-SIGKILL snapshot restore >=3x warmer "
     "than cold restart (bitwise), corrupt snapshot degrades to cold "
     "with zero failures, poison traces quarantined with 422"),
]

#: the subset (and reduced sizes) run by CI's bench-smoke job
SMOKE_KEYS = ("fleet", "sweep", "service", "union", "dispatch", "kernels",
              "frontdoor", "cluster", "optimizer", "chaos", "recovery")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark keys")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: smoke subset at reduced sizes")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="write a machine-readable JSON report (per-bench "
                         "status/duration + the CSV rows) — the nightly "
                         "workflow uploads this as an artifact so "
                         "prediction-error regressions are trackable")
    args = ap.parse_args()
    runtime.use_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {key for key, _, _ in BENCHES}
        if unknown:
            sys.exit(f"unknown benchmark keys: {', '.join(sorted(unknown))}"
                     f" (known: {', '.join(k for k, _, _ in BENCHES)})")
    if args.smoke and only is None:
        only = set(SMOKE_KEYS)

    csv = Csv()
    failed = []
    durations = {}
    t_all = time.time()
    for key, module, title in BENCHES:
        if only and key not in only:
            continue
        print(f"\n=== {title} ===")
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            kwargs = {}
            if args.smoke and \
                    "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            mod.run(csv, **kwargs)
        except Exception as e:  # a failed bench should not kill the run
            import traceback
            print(f"  BENCH FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
            csv.add(f"{key}_FAILED", 0.0, str(type(e).__name__))
            failed.append(key)
        durations[key] = round(time.time() - t0, 2)
        print(f"  [{key}: {durations[key]:.1f}s]")

    print(f"\n=== CSV (name,us_per_call,derived) — total "
          f"{time.time() - t_all:.0f}s ===")
    csv.dump()
    if args.report:
        import json
        report = {
            "smoke": args.smoke,
            "total_seconds": round(time.time() - t_all, 2),
            "failed": failed,
            "durations_seconds": durations,
            "rows": [{"name": n, "us_per_call": round(us, 3),
                      "derived": derived}
                     for n, us, derived in csv.rows],
        }
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)
        print(f"report written to {args.report}")
    if failed:
        # CI gates (smoke) and the nightly full run must fail loudly
        sys.exit(f"benches failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
