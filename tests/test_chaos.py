"""Fault-tolerant serving: deadlines, cancellation, drain, supervision.

Pins the PR-9 robustness contracts:

* spec parsing and zero-cost disarm of the fault-injection registry;
* end-to-end deadlines — immediate 504 shed at admission, per-query
  cancellation when a deadline lapses mid-batch (the batch survives),
  and the coalescing window never stretching past the tightest pending
  deadline;
* fault parity — injected engine-pass errors degrade to per-query
  execution with bitwise-identical answers;
* graceful drain — in-flight work flushes, new work sheds 503 with
  Retry-After, ``/healthz`` flips so routers mark the worker down, and
  a SIGTERMed worker process exits 0 after printing its accounting;
* worker supervision — a killed worker restarts (same port pin) and a
  worker that dies on arrival backs off instead of fork-bombing, and a
  launch interrupted or failed mid-start leaves no worker running;
* router probes — HTTP 5xx on ``/healthz`` is "unhealthy" (alive but
  refusing), a dead transport is "down"; both leave the ring;
* the netcache breaker's half-open ping probe closing the circuit once
  the server is back.
"""

import http.server
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.core import HabitatPredictor, OperationTracker
from repro.serve import faults
from repro.serve.admission import (AdmissionController, DeadlineExceeded,
                                   deadline_scope, remaining_s)
from repro.serve.fleet import FleetPlanner
from repro.serve.http import PredictionClient, PredictionServer
from repro.serve.router import FingerprintRouter
from repro.serve.service import PendingQuery, PredictionService


def _trace(n=12, label="chaos"):
    return OperationTracker("T4").track(
        lambda w, x: jnp.sum(jnp.tanh(x @ w)),
        jnp.zeros((n, 24)), jnp.zeros((8, n)), label=label)


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with the registry disarmed.

    If the *suite* is running with ``REPRO_FAULTS`` armed (CI's chaos
    job), restore that arming on teardown so this module does not
    silently disarm the rest of the run.
    """
    faults.disarm()
    yield
    faults.disarm()
    env_spec = os.environ.get("REPRO_FAULTS", "").strip()
    if env_spec:
        faults.arm(env_spec)


# -- fault spec parsing ------------------------------------------------------
def test_fault_spec_grammar():
    pts = faults.parse_spec(
        "netcache.get_many:delay=200ms,p=0.3;engine.pass:error,p=0.1")
    assert pts["netcache.get_many"].delay_s == pytest.approx(0.2)
    assert pts["netcache.get_many"].p == 0.3
    assert pts["engine.pass"].error is True
    assert pts["engine.pass"].p == pytest.approx(0.1)
    hang = faults.parse_spec("router.forward:hang=1.5s")["router.forward"]
    assert hang.hang_s == pytest.approx(1.5)
    assert hang.error is True               # hang implies a final error
    bare = faults.parse_spec("x:delay=0.25")["x"]
    assert bare.delay_s == pytest.approx(0.25)


@pytest.mark.parametrize("bad", [
    "no-colon-entry",
    "point:unknown=1",
    "point:p=0.5",              # probability without a mode
    "point:error,p=1.5",        # p out of range
])
def test_fault_spec_malformed_fails_loudly(bad):
    with pytest.raises(ValueError):
        faults.parse_spec(bad)


def test_inject_disarmed_is_noop_and_armed_counts():
    faults.inject("engine.pass")            # no-op, no error
    assert faults.stats()["armed"] is False
    faults.arm("engine.pass:error,p=1.0")
    with pytest.raises(faults.FaultInjected):
        faults.inject("engine.pass")
    faults.inject("router.forward")         # unarmed point: still no-op
    st = faults.stats()
    assert st["armed"] is True
    assert st["points"]["engine.pass"]["fired"] == 1
    faults.disarm()
    faults.inject("engine.pass")            # disarmed again


def test_fault_injection_is_deterministic_per_seed():
    def draw(seed):
        faults.arm("p:error,p=0.5", seed=seed)
        out = []
        for _ in range(32):
            try:
                faults.inject("p")
                out.append(0)
            except faults.FaultInjected:
                out.append(1)
        faults.disarm()
        return out

    assert draw(3) == draw(3)
    assert draw(3) != draw(4)


# -- deadlines ---------------------------------------------------------------
def test_resolve_deadline_precedence(monkeypatch):
    svc = PredictionService(predictor=HabitatPredictor(),
                            coalesce_window_ms=0.0)
    assert svc.resolve_deadline({}, None) is None       # unbounded default
    now = time.monotonic()
    d = svc.resolve_deadline({"deadline_ms": 500}, 100.0)
    assert d == pytest.approx(now + 0.5, abs=0.05)      # payload wins
    d = svc.resolve_deadline({}, 100.0)                 # then the header
    assert d == pytest.approx(now + 0.1, abs=0.05)
    assert svc.resolve_deadline({"deadline_ms": 0}, None) is None
    monkeypatch.setenv("REPRO_DEADLINE_MS", "250")
    svc2 = PredictionService(predictor=HabitatPredictor(),
                             coalesce_window_ms=0.0)
    d = svc2.resolve_deadline({}, None)                 # env default last
    assert d == pytest.approx(time.monotonic() + 0.25, abs=0.05)


def test_admission_sheds_504_when_cost_exceeds_budget():
    """A request whose priced cost cannot fit its remaining budget is
    rejected immediately — no queueing, no engine work."""
    svc = PredictionService(predictor=HabitatPredictor(),
                            coalesce_window_ms=0.0)
    tr = _trace()
    passes0 = svc.stats()["engine_passes"]
    with pytest.raises(DeadlineExceeded) as ei:
        svc.rank_request({"trace": tr.to_dict(), "batch_size": 8},
                         deadline_ms=1e-6)
    assert ei.value.status == 504
    s = svc.admission.stats()
    assert s["shed_504"] == 1
    assert s["inflight_requests"] == 0      # nothing leaked
    assert svc.stats()["engine_passes"] == passes0


def test_deadline_lapse_cancels_query_but_batch_survives():
    """One member's lapsed deadline raises 504 for THAT member while the
    shared pass completes bitwise-correct for everyone else."""
    tr_a, tr_b = _trace(10, "dl-a"), _trace(14, "dl-b")
    oracle = FleetPlanner(predictor=HabitatPredictor()).rank(tr_b, 8)
    svc = PredictionService(predictor=HabitatPredictor(),
                            coalesce_window_ms=30.0, flush_at=2,
                            adaptive_window=False)
    svc.rank(tr_a, 8)                       # warm the engine
    faults.arm("engine.pass:delay=250ms,p=1.0")
    results, errors = {}, {}

    def bounded():
        try:
            results["a"] = svc.rank(
                tr_a, 8, deadline=time.monotonic() + 0.05)
        except BaseException as e:
            errors["a"] = e

    def unbounded():
        results["b"] = svc.rank(tr_b, 8)

    threads = [threading.Thread(target=bounded),
               threading.Thread(target=unbounded)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    faults.disarm()
    assert isinstance(errors.get("a"), DeadlineExceeded)
    assert errors["a"].lane == "interactive"
    assert [c.device for c in results["b"]] == \
        [c.device for c in oracle]
    assert [c.iter_ms for c in results["b"]] == \
        [c.iter_ms for c in oracle]
    assert time.monotonic() - t0 < 2.0


def test_coalescing_window_capped_by_tightest_deadline():
    """A 500 ms window must not hold a 60 ms-deadline query hostage:
    the batch fires at the deadline, not the window."""
    svc = PredictionService(predictor=HabitatPredictor(),
                            coalesce_window_ms=500.0, flush_at=64,
                            adaptive_window=False)
    tr = _trace(10, "cap")
    svc.rank(tr, 8)                         # warm (first pass compiles)
    t0 = time.monotonic()
    rows = svc.rank(tr, 8, deadline=time.monotonic() + 0.06)
    dt = time.monotonic() - t0
    assert rows                             # answered, not rejected
    assert dt < 0.4, f"window not capped by deadline ({dt:.3f}s)"


def test_deadline_scope_nests_and_reports_remaining():
    assert remaining_s() is None
    outer = time.monotonic() + 10.0
    with deadline_scope(outer):
        assert 9.0 < remaining_s() < 10.0
        with deadline_scope(time.monotonic() + 1.0):    # innermost wins
            assert remaining_s() < 1.01
        with deadline_scope(None):          # None never widens
            assert 9.0 < remaining_s() < 10.0
        assert 9.0 < remaining_s() < 10.0
    assert remaining_s() is None


# -- finalize protocol -------------------------------------------------------
def test_finish_cancel_exactly_once_under_race():
    """N racing cancels + one finish: exactly one finalizer wins and
    ``on_done`` fires exactly once, every repetition."""
    for rep in range(50):
        fired = []
        q = PendingQuery(kind="rank", traces=[], dests=None,
                         on_done=lambda _q: fired.append(1))
        q.result = "answer"
        barrier = threading.Barrier(5)
        wins = []

        def do_cancel():
            barrier.wait()
            if q.cancel(DeadlineExceeded("lapsed")):
                wins.append("cancel")

        def do_finish():
            barrier.wait()
            q.finish()

        threads = [threading.Thread(target=do_cancel) for _ in range(4)]
        threads.append(threading.Thread(target=do_finish))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(fired) == 1, f"on_done fired {len(fired)}x (rep {rep})"
        assert len(wins) <= 1
        if wins:                            # a cancel won: error delivered
            with pytest.raises(DeadlineExceeded):
                q.get(timeout=0)
        else:                               # finish won: result delivered
            assert q.get(timeout=0) == "answer"


def test_wire_cancel_releases_ticket_exactly_once():
    """A 504-cancelled wire request must return its admission budget —
    completely, and only once — even while the batch is still running."""
    svc = PredictionService(
        predictor=HabitatPredictor(), coalesce_window_ms=0.0,
        admission=AdmissionController(max_queue=64, max_inflight_s=50.0))
    tr = _trace()
    svc.rank(tr, 8)                         # warm
    faults.arm("engine.pass:delay=300ms,p=1.0")
    try:
        with pytest.raises(DeadlineExceeded):
            svc.rank_request({"trace": tr.to_dict(), "batch_size": 8},
                             deadline_ms=40.0)
    finally:
        faults.disarm()
    deadline = time.monotonic() + 2.0       # wait out the slow batch
    while svc.stats()["coalescing"]["executing"] and \
            time.monotonic() < deadline:
        time.sleep(0.01)
    s = svc.admission.stats()
    assert s["inflight_requests"] == 0
    assert s["inflight_cost_s"] == 0.0
    assert s["shed_504"] == 1


# -- graceful drain ----------------------------------------------------------
def test_drain_flushes_inflight_and_sheds_new():
    svc = PredictionService(predictor=HabitatPredictor(),
                            coalesce_window_ms=0.0)
    server = PredictionServer(svc).start()
    client = PredictionClient(server.url)
    tr = _trace(10, "drain")
    oracle = client.rank(tr, batch_size=8)  # warm + oracle
    faults.arm("engine.pass:delay=300ms,p=1.0")
    inflight_result = {}

    def slow_request():
        inflight_result["rows"] = client.rank(tr, batch_size=8)

    t = threading.Thread(target=slow_request)
    try:
        t.start()
        deadline = time.monotonic() + 2.0   # request reached the engine
        while not svc.stats()["coalescing"]["executing"] and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        drained = {}
        d = threading.Thread(
            target=lambda: drained.update(ok=server.drain(timeout=10.0)))
        d.start()
        deadline = time.monotonic() + 2.0
        while not svc.draining and time.monotonic() < deadline:
            time.sleep(0.005)
        assert svc.draining
        # new work sheds 503 + Retry-After while draining...
        with pytest.raises(urllib.error.HTTPError) as ei:
            client.rank(tr, batch_size=8)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["error"] == "draining"
        assert "Retry-After" in ei.value.headers
        ei.value.close()
        # ...and /healthz flips so routers mark the worker down...
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.url + "/healthz", timeout=5)
        assert ei.value.code == 503
        ei.value.close()
        # ...but /stats stays live for the operator
        assert client.stats()["draining"] is True
        t.join(timeout=10)
        d.join(timeout=10)
        assert drained["ok"] is True        # quiesced inside the grace
        assert inflight_result["rows"] == oracle    # in-flight flushed
    finally:
        faults.disarm()
        server.shutdown()


def test_sigterm_drain_exits_zero_with_accounting():
    """The acceptance path: SIGTERM a live worker process — it finishes,
    prints the drain accounting line, and exits 0."""
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve.http", "--port", "0",
         "--coalesce-ms", "0.5"],
        env=env, stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        while line and not line.startswith("serving on "):
            line = proc.stdout.readline()
        assert line, "worker exited before binding"
        url = line.split("serving on ", 1)[1].strip()
        rows = PredictionClient(url, timeout=60.0).rank(
            _trace(10, "sigterm"), batch_size=8)
        assert rows
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=30)
        assert proc.returncode == 0
        assert "drain on shutdown:" in out
        assert "quiesced=True" in out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        proc.stdout.close()


# -- worker supervision ------------------------------------------------------
def test_supervisor_restarts_killed_worker():
    from repro.launch.serve import WorkerSupervisor

    sup = WorkerSupervisor(poll_s=0.05, backoff_s=0.1)
    cmd = [sys.executable, "-u", "-c",
           "print('serving on fake://worker'); "
           "import time; time.sleep(600)"]
    url = sup.spawn(list(cmd))
    assert url == "fake://worker"
    sup.start()
    try:
        sup.procs[0].kill()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            s = sup.stats()
            if s["restarts"] >= 1 and s["per_worker"][0]["alive"]:
                break
            time.sleep(0.02)
        s = sup.stats()
        assert s["restarts"] >= 1
        assert s["per_worker"][0]["alive"]
    finally:
        sup.drain(timeout=5.0)
    assert sup.procs[0].poll() is not None  # drain really stopped it


def test_supervisor_interrupted_spawn_leaves_no_worker(tmp_path):
    """SIGTERM while a worker starts (the launcher turns it into
    KeyboardInterrupt) kills that worker: none outlives the launcher."""
    from repro.launch.serve import WorkerSupervisor

    pid_file = tmp_path / "pid"
    cmd = [sys.executable, "-u", "-c",
           "import os, sys, time; "
           "open(sys.argv[1], 'w').write(str(os.getpid())); "
           "time.sleep(600)", str(pid_file)]

    def _interrupt(signum, frame):
        raise KeyboardInterrupt

    old = signal.signal(signal.SIGALRM, _interrupt)
    signal.setitimer(signal.ITIMER_REAL, 1.0)
    try:
        with pytest.raises(KeyboardInterrupt):
            WorkerSupervisor().spawn(cmd)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
    with pytest.raises(ProcessLookupError):
        os.kill(int(pid_file.read_text()), 0)


def test_supervisor_spawn_all_is_all_or_none():
    """A worker that exits before binding stops the launch, and the
    workers already up are drained with it."""
    from repro.launch.serve import WorkerSupervisor

    sup = WorkerSupervisor(poll_s=0.05)
    up = [sys.executable, "-u", "-c",
          "print('serving on fake://up'); import time; time.sleep(600)"]
    dies = [sys.executable, "-c", "pass"]
    with pytest.raises(SystemExit, match="before binding"):
        sup.spawn_all([up, dies], [sup.env, sup.env])
    assert len(sup.procs) == 1
    assert sup.procs[0].poll() is not None


def test_supervisor_backoff_on_crash_looping_worker():
    """A worker that dies on arrival must not be restarted in a hot
    loop: the per-worker backoff doubles up to its cap."""
    from repro.launch.serve import WorkerSupervisor

    sup = WorkerSupervisor(poll_s=0.02, backoff_s=0.05, backoff_max_s=0.2)
    # prints readiness then exits immediately: every restart "fails"
    cmd = [sys.executable, "-u", "-c", "print('serving on fake://flappy')"]
    sup.spawn(list(cmd))
    sup.start()
    try:
        time.sleep(1.0)
        s = sup.stats()
        # a hot loop would log ~50 restarts in 1s at poll_s=0.02; the
        # doubling backoff (0.05 -> 0.1 -> 0.2 cap) keeps it single-digit
        assert 1 <= s["restarts"] <= 15
        assert sup._workers[0].backoff_s == pytest.approx(0.2)
    finally:
        sup.drain(timeout=5.0)


# -- router probe classification ---------------------------------------------
class _Unhealthy500(http.server.BaseHTTPRequestHandler):
    def do_GET(self):                       # alive process, refusing work
        body = b'{"ok": false}'
        self.send_response(500)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_router_probe_distinguishes_unhealthy_from_down():
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                            _Unhealthy500)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    unhealthy_url = f"http://127.0.0.1:{httpd.server_address[1]}"
    with socket.socket() as s:              # a port with nobody home
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    down_url = f"http://127.0.0.1:{dead_port}"
    try:
        router = FingerprintRouter([unhealthy_url, down_url],
                                   health_s=0.5)
        assert router._probe(unhealthy_url) == "unhealthy"
        assert router._probe(down_url) == "down"
        alive = router.check_health()
        # both leave the ring — but stats tell the operator which is a
        # live-but-refusing process vs a dead host
        assert alive == {unhealthy_url: False, down_url: False}
        st = router.stats()["workers"]
        assert st[unhealthy_url]["state"] == "unhealthy"
        assert st[down_url]["state"] == "down"
        assert router.stats()["live_workers"] == 0
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- netcache breaker half-open probe ----------------------------------------
def test_breaker_half_open_probe_closes_when_server_returns():
    from repro.serve.netcache import CacheServer, NetCache

    server = CacheServer().start()
    port = server.port
    cache = NetCache(f"tcp://127.0.0.1:{port}", timeout_s=0.2,
                     retries=0, backoff_s=0.01, reconnect_s=0.2,
                     probe_s=0.1)
    try:
        cache.put_many([(("k",), 1.25)])
        assert cache.get(("k",)) == 1.25
        assert cache.breaker_state == "closed"
        assert cache.server_stats()["breaker_state"] == "closed"

        server.shutdown()
        assert cache.get_many([("k",)]) == [None]   # degrades to a miss
        assert cache.breaker_state == "open"
        t0 = time.perf_counter()
        assert cache.get_many([("k",)]) == [None]   # breaker short-circuit
        assert time.perf_counter() - t0 < 0.1
        time.sleep(0.3)                     # past max jittered window
        assert cache.breaker_state == "half_open"
        t0 = time.perf_counter()
        assert cache.get_many([("k",)]) == [None]   # probe fails fast
        assert time.perf_counter() - t0 < 0.15      # probe_s, not timeout
        assert cache.breaker_state == "open"        # re-opened w/ jitter

        revived = CacheServer(port=port).start()    # same address
        try:
            time.sleep(0.3)
            assert cache.breaker_state == "half_open"
            assert cache.get(("k",)) is None        # probe closes + serves
            assert cache.breaker_state == "closed"
            cache.put_many([(("k2",), 2.5)])
            assert cache.get(("k2",)) == 2.5
        finally:
            revived.shutdown()
    finally:
        cache.close()


# -- stats surface -----------------------------------------------------------
def test_service_stats_surface_draining_and_faults():
    svc = PredictionService(predictor=HabitatPredictor(),
                            coalesce_window_ms=0.0)
    st = svc.stats()
    assert st["draining"] is False
    assert st["faults"] == {"armed": False, "points": {}}
    assert st["admission"]["shed_504"] == 0
    faults.arm("engine.pass:delay=1ms,p=0.5")
    assert svc.stats()["faults"]["armed"] is True
    assert "engine.pass" in svc.stats()["faults"]["points"]
