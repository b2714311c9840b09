"""Write the trace corpus of one configuration: the inputs users send.

    python benchmarks/chip/make_corpus.py resnet50

Tracks the configuration's training step once per batch size, shape-only
on the CPU, with the program's ``OperationTracker``: the step is
``steps/<step>.py``'s ``make_step(config, batch)``, where ``<step>`` is
the ``step`` of ``configs/<config>.json``.  Prices the ops on each origin
device of the file with the program's simulator.  Writes one gzipped trace
document per (batch, origin) to ``data/<config>/b<batch>.<origin>.json.gz``
and an ``index.json`` beside them.  The serving cells draw their requests
from these fixed files, so a change to the tracker does not change the
benchmark's inputs; only a benchmark change regenerates them.
"""

from __future__ import annotations

import argparse
import copy
import gzip
import importlib
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def write_gz(path: Path, text: str) -> None:
    with open(path, "wb") as f:
        with gzip.GzipFile(fileobj=f, mode="wb", compresslevel=9,
                           mtime=0, filename="") as gz:
            gz.write(text.encode())


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.core.trace import OperationTracker, TrackedTrace

    cfg = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    steps = importlib.import_module(f"benchmarks.chip.steps.{cfg['step']}")
    out = HERE / "data" / cfg["name"]
    out.mkdir(parents=True, exist_ok=True)
    index = []
    for batch in cfg["batch_sizes"]:
        step, params, data = steps.make_step(cfg, batch)
        base = OperationTracker(origin_device=cfg["origins"][0]).track(
            step, params, data, label=f"{cfg['name']}-b{batch}")
        for origin in cfg["origins"]:
            trace = TrackedTrace(ops=copy.deepcopy(base.ops),
                                 origin_device=origin, label=base.label)
            trace.measure("simulate")
            name = f"b{batch}.{origin}.json.gz"
            write_gz(out / name, json.dumps(trace.to_dict(),
                                            separators=(",", ":")))
            index.append({"file": name, "batch": batch, "origin": origin,
                          "ops": len(trace.ops)})
        print(f"{cfg['name']} b{batch}: {len(base.ops)} ops, "
              f"{sum(o.kernel_varying for o in base.ops)} kernel-varying",
              flush=True)
    (out / "index.json").write_text(json.dumps(index, indent=1) + "\n")


if __name__ == "__main__":
    main()
