"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks what the timed path
produced against the benchmark's plain reference, and prints one JSON
object as the last line of stdout.  With ``--trace 0`` its metrics are
the cell's end-to-end metrics; with ``--trace 1``, its per-layer ones,
from a profiled run.  It runs only on a TPU: without one it exits
non-zero and prints no result.

Knee sweep of a serving cell (one server, a window per offered rate):
``--rates 2,4,6``.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rates", type=lambda s: [float(x) for x in s.split(",")],
                    default=None, help="knee sweep: offered rates per second")
    ap.add_argument("--any-platform", action="store_true",
                    help=argparse.SUPPRESS)     # the CPU tests
    ap.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--diag", default=None, metavar="FILE",
                    help="write each request's times and the server's "
                         "spans to FILE (diag.py)")
    args = ap.parse_args(argv)

    from benchmarks.chip import bench

    for need in ("src/repro", "BENCHMARK.json"):
        if not (ROOT / need).exists():
            raise SystemExit(f"run.py: {need} is missing from {ROOT}")
    doc = bench.load_benchmark()
    cell = bench.cell(doc, args.workload)
    config = bench.load_config(cell["config"])
    traffic = bench.load_traffic(cell["traffic"])
    gen = bench.generator(traffic["kind"])
    runner = __import__(f"benchmarks.chip.{gen.RUNNER}",
                        fromlist=["run"])
    child_env = dict(os.environ)
    if gen.RUNNER == "serving":
        # this process stays off the chip: the server child holds it
        os.environ["JAX_PLATFORMS"] = "cpu"
    runner.run(args, doc, cell, config, traffic, gen, STARTED, child_env)


if __name__ == "__main__":
    main()
