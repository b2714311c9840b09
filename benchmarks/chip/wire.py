"""Request bodies built before the window, and what each should answer.

A :class:`Template` splits a corpus document's JSON at its ops' measured
times, so a copy with other times costs one join, not a ``json.dumps`` of
a megabyte.  Floats go out in their shortest ``repr``, which the server's
``json`` reads back to the same double the reference uses.
"""

from __future__ import annotations

import dataclasses
import json
from typing import List, Optional, Tuple

import numpy as np

_MARK = "\x00ms\x00"


class Template:
    def __init__(self, doc: dict):
        self.measured = np.array([op["measured_ms"] for op in doc["ops"]],
                                 np.float64)
        marked = dict(doc, ops=[dict(op, measured_ms=_MARK)
                                for op in doc["ops"]])
        text = json.dumps(marked, separators=(",", ":"))
        self.parts = text.split(json.dumps(_MARK))
        if len(self.parts) != len(self.measured) + 1:
            raise ValueError("a document string holds the template mark")

    def render(self, measured: np.ndarray) -> str:
        out = [self.parts[0]]
        for v, part in zip(measured.tolist(), self.parts[1:]):
            out.append(repr(v))
            out.append(part)
        return "".join(out)


def jittered(base: np.ndarray, rng: np.random.Generator,
             sigma: float) -> np.ndarray:
    """Per-op times times lognormal(0, ``sigma``)."""
    return base * np.exp(sigma * rng.standard_normal(base.shape))


@dataclasses.dataclass
class Request:
    """One request of a stream: when it is due (seconds from the stream's
    start), where it goes, its body, and what the reference needs to
    check the answer: (corpus index, measured times) per trace."""
    due: float
    path: str
    body: bytes
    traces: List[Tuple[int, np.ndarray]]
    batch: Optional[int] = None
    by: Optional[str] = None


@dataclasses.dataclass
class Stream:
    requests: List[Request]             # the measured window
    warmup: List[Request]               # a burst before it, apart
    prefill: List[Request]              # sent once in set-up, in order
    warm_blocks: List[int]              # scorer row-block counts to compile


def arrivals(rate: float, seconds: float, tag: int) -> np.ndarray:
    """``round(rate * seconds)`` Poisson-process arrival times in
    [0, seconds): exponential gaps scaled to the window.  The schedule is
    the same for every seed (``tag`` tells the window from the warm-up):
    runs of different seeds differed far more than two runs of one seed,
    so the seed chooses the traces and not the bursts."""
    rng = np.random.default_rng([0x5EED, tag])
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:-1] / gaps.sum() * seconds


def scorer_rows(docs: list) -> int:
    """The most MLP rows one trace of ``docs`` sends the scorer: its
    kernel-varying ops on every device of the fleet."""
    from benchmarks.chip import reference

    n_dev = len(reference.device_table())
    return max(sum(op["kind"] in reference.MLP_KINDS for op in d["doc"]["ops"])
               for d in docs) * n_dev


def block_buckets(rows_per_request: int, max_coalesce: int,
                  block_m: int = 128) -> List[int]:
    """Row-block counts to compile the scorer at: powers of two to 32 and
    multiples of 32 up to what ``max_coalesce`` coalesced requests need."""
    top = -(-rows_per_request * max_coalesce // block_m)
    out = [1, 2, 4, 8, 16, 32]
    while out[-1] < top:
        out.append(out[-1] + 32)
    return out
