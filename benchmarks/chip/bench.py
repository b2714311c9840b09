"""What every cell shares: the benchmark file, finding a cell's pieces by
name, statistics and the result line.

A cell's configuration is ``configs/<config>.json``; its traffic mix is
``traffic/<traffic>.json``, whose ``kind`` names the generator
``gen/<kind>.py``; the generator's ``RUNNER`` names the module that runs
the cell (``serving``).  A per-layer metric ``<m>`` is
read by ``metrics/<m>.py``, or, where there is none, by the reader of its
family ``metrics/<m up to the first dot>.py``.  Adding a configuration, a
mix or a metric adds files and entries; it edits none of these.
"""

from __future__ import annotations

import gzip
import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def generator(kind: str):
    return importlib.import_module(f"benchmarks.chip.gen.{kind}")


def _load_file(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The module whose ``read(ctx)`` gives metric ``name`` (or None)."""
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            return _load_file(path, f"chipbench_metric_{stem.replace('.', '_')}")
    raise SystemExit(f"no reader for metric {name!r} under metrics/")


def metric_spec(bench: dict, group: str, workload: str) -> List[dict]:
    """The ``group`` ("end_to_end" | "per_layer") metrics ``workload``
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["peaks"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


# -- corpus ------------------------------------------------------------------
def corpus(config: dict) -> List[dict]:
    """The config's trace documents, in ``index.json`` order, each with
    its ``batch`` and ``origin``."""
    base = HERE / "data" / config["name"]
    out = []
    for entry in json.loads((base / "index.json").read_text()):
        doc = json.loads(gzip.decompress((base / entry["file"]).read_bytes()))
        out.append({"batch": entry["batch"], "origin": entry["origin"],
                    "doc": doc})
    return out


# -- statistics ----------------------------------------------------------------
def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q`` quantile by nearest rank; failed samples are ``inf``."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q * len(v)) - 1)]


def seed_words(seed: int, *tags: int) -> List[int]:
    """An entropy list for ``numpy.random.SeedSequence``: seeds past 32
    bits are fine, and each tag gives an independent stream."""
    return [seed & 0xFFFFFFFF, seed >> 32, *tags]


# -- result ------------------------------------------------------------------
def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, tuple], device: dict,
                checks: List[dict], breakdown: Optional[dict] = None) -> None:
    """Print the compared numbers to stderr, then the JSON result as the
    last line of stdout (the checks under their own key, last)."""
    for c in checks:
        say(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": u}
                       for k, (v, u) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    try:
        line = json.dumps(out, allow_nan=False)
    except ValueError:
        # a quantile that falls on a missing (shed or failed) request
        raise SystemExit(f"a metric is not finite: {out['metrics']}")
    print(line, flush=True)
