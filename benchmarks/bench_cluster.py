"""Cluster gate: N worker processes, no shared filesystem, one netcache.

The cross-host serving tier's acceptance bench (``serve/netcache.py`` +
``serve/router.py``).  Everything here crosses real process boundaries:
a standalone cache-server process (``python -m repro.serve.netcache``),
>= 3 worker processes (``python -m repro.serve.http --cache tcp://...``)
that share NOTHING but that TCP connection — no sqlite file, no common
tmpdir — and an in-process router face fronting them.

Phase A — cross-worker warmth: a repeated-trace burst where round ``r``
sends trace ``j`` to worker ``(r + j) % N``, so every repeat lands on a
*different* worker than the one that priced it.  Gate: the cache
server's GLOBAL hit rate >= 50% (repeats must be network-cache hits,
not recomputes), and every answer is bitwise-identical to an in-process
``FleetPlanner`` oracle — the network cache round-trips float64 exactly.

Phase B — failover: a threaded burst through the fingerprint router
with one worker SIGKILLed mid-burst.  Gate: **zero lost requests** (the
router re-hashes transport failures onto surviving workers), answers
stay bitwise-correct, and the post-kill p99 stays bounded (a kill may
cost one connect-failure round-trip, never a hang).
"""

from __future__ import annotations

import sys
from pathlib import Path

if __package__ in (None, ""):   # direct invocation: python benchmarks/...
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(_ROOT))
    sys.path.insert(0, str(_ROOT / "src"))

import os
import subprocess
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from benchmarks.common import Csv
from benchmarks.bench_fleet import synthetic_trace
from repro import runtime
from repro.core import HabitatPredictor
from repro.serve.fleet import FleetPlanner
from repro.serve.http import PredictionClient
from repro.serve.netcache import NetCache
from repro.serve.router import FingerprintRouter, RouterServer

_N_WORKERS = 3
_BATCH = 32


def _spawn(mod: str, extra: List[str], readiness: str
           ) -> Tuple[subprocess.Popen, str]:
    """Launch ``python -m mod`` and parse its readiness line for the
    bound address (``--port 0`` everywhere: no port races).  Neither the
    cache server nor an MLP-free worker runs a JAX computation, so each
    is kept off the TPU chips (``runtime.worker_envs``)."""
    env = runtime.worker_envs(1, uses_device=False)[0]
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.Popen(
        [sys.executable, "-m", mod, "--port", "0", *extra],
        env=env, stdout=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    while line and not line.startswith(readiness):
        line = proc.stdout.readline()
    if not line:
        proc.terminate()
        proc.wait()
        proc.stdout.close()
        raise RuntimeError(f"{mod} exited before binding its port")
    return proc, line.split("serving on ", 1)[1].strip()


def _reap(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.terminate()
    proc.wait()
    proc.stdout.close()


def _assert_bitwise(rows, oracle, where: str) -> None:
    """A served ranking must be byte-for-byte the in-process answer."""
    if [r["device"] for r in rows] != [c.device for c in oracle]:
        raise AssertionError(f"{where}: device order diverged")
    for r, c in zip(rows, oracle):
        if r["iter_ms"] != c.iter_ms:
            raise AssertionError(
                f"{where}: iter_ms not bitwise ({r['device']}: "
                f"{r['iter_ms']!r} != {c.iter_ms!r})")


def run(csv: Csv, smoke: bool = False) -> None:
    n_traces = 4 if smoke else 8
    n_rounds = 3 if smoke else 4
    n_burst = 48 if smoke else 160
    kill_after = n_burst // 3

    traces = [synthetic_trace(20 + 2 * i, origin="T4", seed=700 + i)
              for i in range(n_traces)]
    planner = FleetPlanner(predictor=HabitatPredictor())
    oracles = [planner.rank(t, batch_size=_BATCH) for t in traces]

    cache_proc, cache_url = _spawn("repro.serve.netcache", [], "serving on ")
    workers, urls = [], []
    try:
        for _ in range(_N_WORKERS):
            proc, url = _spawn(
                "repro.serve.http",
                ["--cache", cache_url, "--coalesce-ms", "0.5"],
                "serving on ")
            workers.append(proc)
            urls.append(url)
        clients = [PredictionClient(u, timeout=120.0) for u in urls]
        probe = NetCache(cache_url)     # reads the server's GLOBAL stats

        # -- phase A: repeated-trace burst, repeats on OTHER workers ------
        t0 = time.perf_counter()
        n_reqs = 0
        for r in range(n_rounds):
            for j, trace in enumerate(traces):
                rows = clients[(r + j) % _N_WORKERS].rank(
                    trace, batch_size=_BATCH)
                _assert_bitwise(rows, oracles[j],
                                f"phase A round {r} trace {j}")
                n_reqs += 1
        dt_a = time.perf_counter() - t0
        server = probe.server_stats()
        if server is None:
            raise AssertionError("cache server unreachable after burst")
        hit_rate = server["hit_rate"]
        print(f"  phase A     : {n_reqs} reqs over {_N_WORKERS} workers in "
              f"{dt_a:.2f}s | netcache hits={server['hits']} "
              f"misses={server['misses']} hit_rate={hit_rate:.0%} "
              f"entries={server['entries']}")
        # round 1 primes (misses), every later round re-asks from a
        # different worker: (n_rounds-1)/n_rounds of probes must hit
        if hit_rate < 0.5:
            raise AssertionError(
                f"cross-worker hit rate {hit_rate:.0%} < 50% — repeats "
                f"are being recomputed, not served from the netcache")

        # -- phase B: router burst with a mid-burst worker kill -----------
        router = FingerprintRouter(urls, health_s=0.5)
        face = RouterServer(router).start()
        rclient = PredictionClient(face.url, timeout=120.0)
        lock = threading.Lock()
        latencies: List[Tuple[int, float]] = []
        errors: List[str] = []
        fired = threading.Event()
        n_threads = 4

        def burst(k: int) -> None:
            for i in range(k, n_burst, n_threads):
                if i >= kill_after:
                    fired.wait()    # kill lands strictly mid-burst
                j = i % n_traces
                t1 = time.perf_counter()
                try:
                    rows = rclient.rank(traces[j], batch_size=_BATCH)
                    _assert_bitwise(rows, oracles[j], f"phase B req {i}")
                except Exception as e:      # a lost request fails the gate
                    with lock:
                        errors.append(f"req {i}: {type(e).__name__}: {e}")
                    continue
                with lock:
                    latencies.append((i, time.perf_counter() - t1))

        threads = [threading.Thread(target=burst, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        while True:     # kill once the pre-kill portion has completed
            with lock:
                done = sum(i < kill_after for i, _ in latencies)
            if done + len(errors) >= kill_after - n_threads:
                break
            time.sleep(0.01)
        workers[0].kill()   # SIGKILL: no graceful close, sockets just die
        fired.set()
        for t in threads:
            t.join()

        pre = [dt for i, dt in latencies if i < kill_after]
        post = [dt for i, dt in latencies if i >= kill_after]
        rstats = router.stats()
        face.shutdown()
        if errors:
            raise AssertionError(
                f"lost {len(errors)}/{n_burst} requests across the worker "
                f"kill (first: {errors[0]})")
        if len(latencies) != n_burst:
            raise AssertionError(
                f"only {len(latencies)}/{n_burst} answers recorded")
        if rstats["live_workers"] != _N_WORKERS - 1:
            raise AssertionError(
                f"router still lists {rstats['live_workers']} live workers "
                f"after the kill (expected {_N_WORKERS - 1})")
        p99_pre = float(np.percentile(pre, 99))
        p99_post = float(np.percentile(post, 99))
        # one failover costs a refused connect + a retry, never a hang:
        # generous absolute floor because pre-kill p99 is sub-10ms here
        p99_bound = max(10.0 * p99_pre, 2.0)
        if p99_post > p99_bound:
            raise AssertionError(
                f"post-kill p99 unbounded: {p99_post * 1e3:.0f} ms "
                f"(bound {p99_bound * 1e3:.0f} ms)")
        print(f"  phase B     : {n_burst} reqs, worker 0 SIGKILLed after "
              f"{kill_after} | lost 0 | failovers={rstats['failovers']} | "
              f"p99 {p99_pre * 1e3:.1f} -> {p99_post * 1e3:.1f} ms "
              f"(bound {p99_bound * 1e3:.0f} ms)")
        server_b = probe.server_stats()
        print(f"  netcache    : hit_rate={server_b['hit_rate']:.0%} "
              f"entries={server_b['entries']} after failover re-serves")
        probe.close()

        csv.add("cluster_warmth", dt_a / n_reqs * 1e6,
                f"hit{hit_rate:.2f}_{_N_WORKERS}workers")
        csv.add("cluster_failover", p99_post * 1e6,
                f"lost0_failovers{rstats['failovers']}"
                f"_p99pre{p99_pre * 1e3:.1f}ms")
    finally:
        for proc in workers:
            _reap(proc)
        _reap(cache_proc)


if __name__ == "__main__":
    run(Csv(), smoke="--smoke" in sys.argv)
