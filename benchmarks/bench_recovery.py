"""Durability gate: warm restore, corruption degrade, poison quarantine.

The durable-warm-state acceptance bench (``serve/snapshot.py`` +
``core/integrity.py`` + the service's poison-trace quarantine).  Three
phases, each a hard gate:

Phase A — warm restore beats cold restart: two supervised workers are
warmed with the same traffic, then SIGKILLed.  One carries
``--snapshot`` (periodic warm-state snapshots); the other is the cold
control.  Both run with the wire-level response cache enabled
(``REPRO_RESPONSE_CACHE``), so the restored worker answers the replay
at wire speed from its restored response cache while the control
re-parses and re-predicts everything.  Gate: zero failed requests
across both kill/restart cycles, the restored worker's replay is
served from restored state (response-cache hit delta >= traces, the
control misses everything), its replay p50 is >= 3x faster than the
cold control's, every restored answer is bitwise-identical to the
pre-kill answer, and a dests-variant replay (different payload bytes,
same cells) proves the PLANNER cache restored too — it must hit, not
recompute, and still answer bitwise.

Phase B — corruption degrades to cold: the snapshot file is overwritten
with garbage between the kill and the restart.  Gate: the worker still
comes up (restore never raises into startup), ``/stats`` shows
``integrity.corrupt_snapshot`` >= 1 and ``snapshot.restored`` false,
and the full replay succeeds with ZERO failed requests — corruption
costs warmth, never availability.

Phase C — poison-trace quarantine: a trace that passes wire validation
but crashes the engine (unknown origin device) is hammered through the
threaded front end.  Gate: the first ``REPRO_QUARANTINE_THRESHOLD``
attempts answer 4xx from the engine-failure path, every later attempt
answers a structured 422 (``code: quarantined``, ``Retry-After``)
WITHOUT reaching the engine, and healthy-trace goodput stays 100%
bitwise-correct throughout the burst.
"""

from __future__ import annotations

import sys
from pathlib import Path

if __package__ in (None, ""):   # direct invocation: python benchmarks/...
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(_ROOT))
    sys.path.insert(0, str(_ROOT / "src"))

import json
import statistics
import tempfile
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

from benchmarks.common import Csv
from benchmarks.bench_fleet import synthetic_trace
from repro import runtime
from repro.core import HabitatPredictor
from repro.launch.serve import WorkerSupervisor, _worker_env
from repro.serve.http import PredictionClient, PredictionServer
from repro.serve.service import PredictionService

_BATCH = 32


def _wait_restarted(sup: WorkerSupervisor, idx: int, url: str,
                    min_restarts: int, timeout: float = 90.0) -> None:
    """Block until worker ``idx`` restarted and answers /healthz."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        s = sup.stats()["per_worker"][idx]
        if s["restarts"] >= min_restarts and s["alive"]:
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=0.5) as r:
                    if r.status == 200:
                        return
            except OSError:
                pass
        time.sleep(0.05)
    raise AssertionError(
        f"worker {idx} not back within {timeout:.0f}s of SIGKILL")


def _post_raw(url: str, path: str, body: bytes,
              timeout: float = 120.0) -> bytes:
    req = urllib.request.Request(
        url + path, data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read()


def _rank_bodies(traces, dests=None) -> List[bytes]:
    """Prebuilt /rank bodies — encoded ONCE so the replay measures the
    server, not the client's per-call trace serialization (a constant
    both workers would pay identically)."""
    out = []
    for t in traces:
        p = {"trace": t.to_dict(), "batch_size": _BATCH}
        if dests is not None:
            p["dests"] = list(dests)
        out.append(json.dumps(p).encode())
    return out


def _replay(url: str, bodies: List[bytes]
            ) -> Tuple[List[bytes], List[float]]:
    """POST every body twice; returns (first-pass responses, rep-0 walls).

    Only the FIRST pass is timed: that is the recovery-relevant traffic
    (the worker's first sight of each request after a restart).  The
    second pass exists to fill the response cache either way, so both
    workers snapshot/serve comparable state.  Answers are the raw
    response BYTES — the bitwise gates compare them directly."""
    answers, walls = [], []
    for rep in range(2):
        for b in bodies:
            t0 = time.perf_counter()
            text = _post_raw(url, "/rank", b)
            if rep == 0:
                walls.append(time.perf_counter() - t0)
                answers.append(text)
    return answers, walls


def _phase_ab(csv: Csv, smoke: bool) -> None:
    n_traces = 4 if smoke else 6
    # traces big enough that a cold request's decode + engine pass
    # clearly dominates the ~1 ms transport floor both workers share
    traces = [synthetic_trace(200 + 30 * i, origin="T4", seed=700 + i)
              for i in range(n_traces)]

    tmp = Path(tempfile.mkdtemp(prefix="bench-recovery-"))
    snap_path = tmp / "worker-0.snap"
    env = runtime.worker_envs(1, uses_device=False, base=_worker_env())[0]
    env["REPRO_SNAPSHOT_INTERVAL_S"] = "0.2"
    # pin the adaptive coalescing window: under this bench's solo traffic
    # it would stretch to REPRO_WINDOW_MAX_MS (25 ms) and bury the
    # engine-warmth signal the p50 gate measures under a fixed wait
    env["REPRO_WINDOW_MAX_MS"] = "0"
    # both workers get the wire-level response cache; only the snapshot
    # worker's entries survive the SIGKILL
    env["REPRO_RESPONSE_CACHE"] = "512"
    sup = WorkerSupervisor(poll_s=0.1, backoff_s=0.2, env=env)
    base_cmd = [sys.executable, "-m", "repro.serve.http",
                "--host", "127.0.0.1", "--port", "0",
                "--coalesce-ms", "0.5"]
    url_warm = sup.spawn(base_cmd + ["--snapshot", str(snap_path)])
    url_cold = sup.spawn(list(base_cmd))
    sup.start()
    try:
        warm = PredictionClient(url_warm, timeout=120.0)
        cold = PredictionClient(url_cold, timeout=120.0)
        bodies = _rank_bodies(traces)

        # warm both workers with the same traffic; the snapshot worker's
        # answers are the bitwise oracle for the post-restore replay
        oracle, _ = _replay(url_warm, bodies)
        _replay(url_cold, bodies)

        # wait for a snapshot taken AFTER warming (0.2 s interval) — a
        # save from before the warmup finished would miss warm entries
        saves_before = warm.stats()["snapshot"]["saves"]
        saves0 = saves_before
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            saves0 = warm.stats()["snapshot"]["saves"]
            if saves0 > saves_before and snap_path.exists():
                break
            time.sleep(0.05)
        if saves0 <= saves_before:
            raise AssertionError("no post-warmup snapshot within 15s "
                                 "(interval 0.2s)")

        # ---- phase A: SIGKILL both, replay, compare warmth ------------
        for proc in sup.procs:
            proc.kill()
        t_kill = time.monotonic()
        _wait_restarted(sup, 0, url_warm, min_restarts=1)
        _wait_restarted(sup, 1, url_cold, min_restarts=1)
        t_up = time.monotonic()

        st_warm = warm.stats()
        if not st_warm["snapshot"]["restored"]:
            raise AssertionError("restarted worker did not restore its "
                                 "snapshot before readiness")
        rhits0_w = st_warm["response_cache"]["hits"]
        phits0_w = st_warm["cache"]["hits"]
        st_cold = cold.stats()
        misses0_c = st_cold["cache"]["misses"]

        restored, walls_warm = _replay(url_warm, bodies)
        _, walls_cold = _replay(url_cold, bodies)

        for i, text in enumerate(restored):
            if text != oracle[i]:
                raise AssertionError(
                    f"restored answer for trace {i} diverged from the "
                    f"pre-kill answer (restore must be bitwise)")
        rhits_w = warm.stats()["response_cache"]["hits"] - rhits0_w
        misses_c = cold.stats()["cache"]["misses"] - misses0_c
        if rhits_w < n_traces:
            raise AssertionError(
                f"restored worker served only {rhits_w} response-cache "
                f"hits across the replay (expected >= {n_traces}: the "
                f"restored response cache must carry the repeat traffic)")
        if misses_c < n_traces:
            raise AssertionError(
                f"cold control missed only {misses_c} times — the "
                f"control is not actually cold; the comparison is void")
        p50_w = statistics.median(walls_warm)
        p50_c = statistics.median(walls_cold)
        ratio = p50_c / p50_w if p50_w > 0 else float("inf")
        print(f"  phase A     : {n_traces} traces, both workers "
              f"SIGKILLed, back in {t_up - t_kill:.1f}s | restored "
              f"{st_warm['snapshot']['restored_entries']} entries | "
              f"replay p50 warm {p50_w * 1e3:.1f} ms vs cold "
              f"{p50_c * 1e3:.1f} ms ({ratio:.1f}x) | response hits "
              f"warm={rhits_w} cold misses={misses_c} | bitwise "
              f"identical to pre-kill")
        if ratio < 3.0:
            raise AssertionError(
                f"restored replay only {ratio:.1f}x faster than the cold "
                f"control (gate: >= 3x)")

        # dests-variant replay: different payload bytes (response-cache
        # MISS) over the same cells — only the restored PLANNER cache
        # can answer it without recomputing, and it must stay bitwise
        devs = [r["device"]
                for r in json.loads(oracle[0])["ranking"]]
        variant_walls = []
        for i, body in enumerate(_rank_bodies(traces, dests=devs)):
            t0 = time.perf_counter()
            text = _post_raw(url_warm, "/rank", body)
            variant_walls.append(time.perf_counter() - t0)
            if text != oracle[i]:
                raise AssertionError(
                    f"dests-variant answer for trace {i} diverged — the "
                    f"restored planner cache returned different cells")
        phits_w = warm.stats()["cache"]["hits"] - phits0_w
        if phits_w < n_traces:
            raise AssertionError(
                f"dests-variant replay scored only {phits_w} planner-"
                f"cache hits (expected >= {n_traces}: the snapshot must "
                f"restore the planner cache, not just responses)")
        print(f"  phase A'    : dests-variant replay p50 "
              f"{statistics.median(variant_walls) * 1e3:.1f} ms | "
              f"planner hits {phits_w} | bitwise identical — planner "
              f"cache restored too")
        csv.add("recovery_warm_restore", p50_w * 1e6,
                f"{ratio:.1f}x_rhits{rhits_w}_phits{phits_w}")

        # ---- phase B: corrupt the snapshot, kill, must come up cold ---
        sup.procs[0].kill()
        # the restarting worker spends seconds in imports before it
        # reads the snapshot — overwrite it with garbage first
        snap_path.write_bytes(b"RSB1" + b"\x00" * 64)
        _wait_restarted(sup, 0, url_warm, min_restarts=2)
        st = warm.stats()
        if st["integrity"]["corrupt_snapshot"] < 1:
            raise AssertionError("corrupt snapshot not detected "
                                 "(integrity.corrupt_snapshot == 0)")
        if st["snapshot"]["restored"]:
            raise AssertionError("worker claims it restored a snapshot "
                                 "that was garbage")
        failed = 0
        answers, _ = _replay(url_warm, bodies)
        for i, text in enumerate(answers):
            if text != oracle[i]:
                failed += 1
        if failed:
            raise AssertionError(
                f"{failed} cold recomputed answers diverged from the "
                f"oracle after snapshot corruption")
        print(f"  phase B     : snapshot corrupted between kill and "
              f"restart | worker up, started cold "
              f"(corrupt_snapshot="
              f"{st['integrity']['corrupt_snapshot']}) | "
              f"{2 * n_traces} replay requests, 0 failed, all bitwise")
        csv.add("recovery_corrupt_cold", 0.0,
                f"corrupt{st['integrity']['corrupt_snapshot']}_failed0")
    finally:
        sup.drain()


def _post_status(url: str, path: str, payload: Dict
                 ) -> Tuple[int, Dict, Optional[str]]:
    """POST; returns (status, body, retry_after) without raising."""
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60.0) as resp:
            return (resp.status, json.loads(resp.read()),
                    resp.headers.get("Retry-After"))
    except urllib.error.HTTPError as e:
        return (e.code, json.loads(e.read()),
                e.headers.get("Retry-After"))


def _phase_c(csv: Csv, smoke: bool) -> None:
    n_poison = 8 if smoke else 16
    healthy = [synthetic_trace(18 + 2 * i, origin="T4", seed=770 + i)
               for i in range(3)]
    service = PredictionService(predictor=HabitatPredictor(),
                                coalesce_window_ms=0.0,
                                adaptive_window=False)
    threshold = service.quarantine_threshold
    server = PredictionServer(service).start()
    try:
        oracle = [_post_status(server.url, "/rank",
                               {"trace": t.to_dict(), "batch_size": _BATCH})
                  for t in healthy]
        for status, _, _ in oracle:
            if status != 200:
                raise AssertionError("healthy warmup failed")

        poison = healthy[0].to_dict()
        poison["origin_device"] = "GPU-THAT-NEVER-WAS"     # valid wire,
        # unknown to the device registry -> crashes in the engine
        passes0 = service.planner.engine_pass_count()
        statuses = []
        for i in range(n_poison):
            status, body, retry = _post_status(
                server.url, "/rank",
                {"trace": poison, "batch_size": _BATCH})
            statuses.append(status)
            if i >= threshold:
                if status != 422:
                    raise AssertionError(
                        f"poison attempt {i} answered {status}, expected "
                        f"422 after {threshold} crashes: {body}")
                if body.get("code") != "quarantined" or retry is None:
                    raise AssertionError(
                        f"422 body/headers not structured: {body}")
            # healthy traffic interleaves and must stay bitwise-stable
            j = i % len(healthy)
            status, body, _ = _post_status(
                server.url, "/rank",
                {"trace": healthy[j].to_dict(), "batch_size": _BATCH})
            if status != 200 or body != oracle[j][1]:
                raise AssertionError(
                    f"healthy trace {j} degraded during the poison burst "
                    f"(status {status})")
        quarantined_passes = (service.planner.engine_pass_count()
                              - passes0)
        qs = service.stats()["quarantine"]
        if qs["active"] < 1 or qs["rejected"] < n_poison - threshold:
            raise AssertionError(f"quarantine accounting wrong: {qs}")
        print(f"  phase C     : {n_poison} poison attempts | first "
              f"{threshold} hit the engine "
              f"({statuses[:threshold]}), the rest answered 422 "
              f"({qs['rejected']} rejected at the door) | healthy "
              f"goodput 100% bitwise throughout")
        csv.add("recovery_quarantine", 0.0,
                f"rejected{qs['rejected']}_passes{quarantined_passes}")
    finally:
        server.shutdown()


def run(csv: Csv, smoke: bool = False) -> None:
    _phase_ab(csv, smoke)
    _phase_c(csv, smoke)


if __name__ == "__main__":
    run(Csv(), smoke="--smoke" in sys.argv)
