"""Open-loop ``/rank`` traffic over a configuration's corpus.

Parameters (``traffic/<mix>.json``): ``rate_per_s`` (Poisson arrivals,
see ``wire.arrivals``), ``jitter_sigma`` (per-op lognormal spread of the
measured times, so that a copy is a trace never sent before), ``by``
(objectives, drawn uniformly), ``population`` (null: every request a
fresh copy; n: n fixed copies under Zipf popularity ``zipf_s``, each sent
once in set-up, and ``fresh`` requests of the window, at places drawn
from the seed, fresh copies that reach the scorer), ``warmup_s`` (a burst
at the same rate before the window, drawn apart from it) and
``max_coalesce`` (requests per engine pass the scorer is compiled for).
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks.chip import bench, wire

RUNNER = "serving"
WINDOW, WARMUP, POPULATION = 1, 2, 3


def _body(tpl: wire.Template, measured, batch: int, by: str) -> bytes:
    return ('{"trace":' + tpl.render(measured) + ',"batch_size":'
            + str(int(batch)) + ',"by":' + json.dumps(by) + "}").encode()


def make(traffic: dict, config: dict, docs: list, seed: int,
         seconds: float, templates: list) -> wire.Stream:
    sigma = traffic["jitter_sigma"]
    by = traffic["by"]

    def fresh(rng):
        i = int(rng.integers(len(docs)))
        return i, wire.jittered(templates[i].measured, rng, sigma)

    def request(rng, due, pick):
        i, measured = pick(rng)
        b, o = docs[i]["batch"], by[int(rng.integers(len(by)))]
        return wire.Request(due=float(due), path="/rank",
                            body=_body(templates[i], measured, b, o),
                            traces=[(i, measured)], batch=b, by=o)

    prefill, pick = [], fresh
    if traffic.get("population"):
        prng = np.random.default_rng(bench.seed_words(seed, POPULATION))
        pop = [fresh(prng) for _ in range(traffic["population"])]
        ranks = np.arange(1, len(pop) + 1, dtype=np.float64)
        p = ranks ** -traffic["zipf_s"]
        p /= p.sum()
        order = prng.permutation(len(pop))  # popularity is not corpus order
        prefill = [request(prng, 0.0, lambda r, k=k: pop[k])
                   for k in range(len(pop))]

        def pick(rng):
            return pop[order[int(rng.choice(len(pop), p=p))]]

    def stream(tag, length, fresh_n=0):
        rng = np.random.default_rng(bench.seed_words(seed, tag))
        times = wire.arrivals(traffic["rate_per_s"], length, tag)
        new = set(rng.choice(len(times), size=min(fresh_n, len(times)),
                             replace=False).tolist())
        return [request(rng, t, fresh if k in new else pick)
                for k, t in enumerate(times)]

    return wire.Stream(
        requests=stream(WINDOW, seconds, traffic.get("fresh", 0)),
        warmup=stream(WARMUP, traffic["warmup_s"]),
        prefill=prefill,
        warm_blocks=wire.block_buckets(wire.scorer_rows(docs),
                                       traffic["max_coalesce"]))
