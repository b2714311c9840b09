"""Sharded, asynchronous checkpointing with elastic restore.

Layout: ``<dir>/step_<N>/arrays.npz`` (flattened pytree, one entry per
leaf, gathered to host) + ``meta.json`` (step, tree structure, config
name).  Writes happen on a background thread (*async checkpointing*: the
train loop only blocks on device->host transfer of the snapshot, not the
filesystem).  ``restore`` re-shards onto whatever mesh the caller provides,
which is what makes 8-device checkpoints restorable on 4 devices (elastic
re-scale) — tested in tests/test_fault_tolerance.py.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Optional, Tuple

import jax
import numpy as np

_SEP = "|"


def _flatten(tree: Any):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = _SEP.join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        # a copy, not a view: the trainer donates the live state to the
        # next step while the writer thread still reads this snapshot
        out[key] = np.array(leaf, copy=True)
    return out, treedef


def save(directory: str, step: int, tree: Any,
         blocking: bool = True) -> threading.Thread:
    """Snapshot ``tree`` under ``directory/step_<step>`` atomically."""
    arrays, _ = _flatten(tree)
    target = Path(directory) / f"step_{step}"
    tmp = Path(directory) / f".tmp_step_{step}"

    def write():
        tmp.mkdir(parents=True, exist_ok=True)
        np.savez(tmp / "arrays.npz", **arrays)
        (tmp / "meta.json").write_text(json.dumps(
            {"step": step, "time": time.time(),
             "keys": sorted(arrays)}))
        if target.exists():
            shutil.rmtree(target)
        tmp.rename(target)

    thread = threading.Thread(target=write, daemon=True)
    thread.start()
    if blocking:
        thread.join()
    return thread


def latest_step(directory: str) -> Optional[int]:
    d = Path(directory)
    if not d.exists():
        return None
    steps = [int(p.name.split("_", 1)[1]) for p in d.glob("step_*")
             if (p / "meta.json").exists()]
    return max(steps) if steps else None


def restore(directory: str, like: Any, step: Optional[int] = None,
            shardings: Any = None) -> Tuple[Any, int]:
    """Restore into the structure of ``like``; re-shard via ``shardings``.

    ``shardings`` (same pytree structure, of jax.sharding.Sharding) may
    target a *different* mesh than the one the checkpoint was written from
    — this is the elastic-rescale path."""
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {directory}")
    path = Path(directory) / f"step_{step}"
    data = np.load(path / "arrays.npz")
    _, treedef = _flatten(like)
    leaves = []
    flat, _ = jax.tree_util.tree_flatten_with_path(like)
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(flat))
    for i, (pth, ref) in enumerate(flat):
        key = _SEP.join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in pth)
        arr = data[key]
        if arr.shape != np.shape(ref):
            raise ValueError(f"shape mismatch for {key}: checkpoint "
                             f"{arr.shape} vs model {np.shape(ref)}")
        arr = arr.astype(np.asarray(ref).dtype if not hasattr(ref, "dtype")
                         else ref.dtype)
        if shard_leaves[i] is not None:
            arr = jax.device_put(arr, shard_leaves[i])
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves), step
