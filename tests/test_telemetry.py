"""The span registry (``repro.telemetry``): totals, the thread rules, the
collector hook, and the spans of one served ``/rank`` in a profiler trace."""

import gc
import glob
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import HabitatPredictor, OperationTracker, mlp
from repro.serve.http import PredictionClient, PredictionServer
from repro.serve.service import PredictionService

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import profile  # noqa: E402

#: the spans and waits every cold ``/rank`` through the threaded front
#: door records once
RANK_PATH = ("http.read", "http.reply", "rank.lookup", "rank.decode",
             "rank.admit", "rank.queue", "rank.wait", "rank.encode",
             "engine.pass", "engine.score")
WAITS = ("rank.queue", "rank.wait")


def _trace(width):
    """A trace never ranked before in this process (one per width)."""
    return OperationTracker("T4").track(
        lambda w, x: jnp.sum(jnp.tanh(x @ w)),
        jnp.zeros((8, width)), jnp.zeros((8, 8)), label=f"t{width}")


def _scoring_predictor():
    """Tiny random MLPs behind the fused scorer's jnp lowering, so that a
    cold rank calls the scorer."""
    cfg = mlp.MLPConfig(in_features=13, hidden_layers=2, hidden_size=32)
    mlps = {kind: mlp.TrainedMLP(kind=kind, cfg=cfg,
                                 params=mlp.init_params(cfg),
                                 feature_mean=np.zeros(13),
                                 feature_std=np.ones(13))
            for kind in ("conv2d", "linear", "bmm", "recurrent")}
    return HabitatPredictor(mlps=mlps, sweep_scorer="jnp")


def _counts(stats):
    return {n: stats[n]["count"] for n in telemetry.NAMES}


def _profiled(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` session; the trace's events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    return files[0]


def _program_events(host):
    return sorted(e for e in host if e[2] in telemetry.NAMES)


@pytest.fixture
def hooked():
    """A fresh registry with its collector hook, removed afterwards."""
    reg = telemetry.Registry()
    reg.install_gc_hook()
    try:
        yield reg
    finally:
        gc.callbacks.remove(reg._on_gc)


# -- totals ----------------------------------------------------------------------
def test_nested_spans_keep_inclusive_and_self_time():
    reg = telemetry.Registry()
    with reg.span("engine.pass") as outer:
        time.sleep(0.02)
        with reg.span("engine.score") as inner:
            time.sleep(0.03)
    s = reg.stats()
    assert s["engine.pass"]["count"] == s["engine.score"]["count"] == 1
    assert s["engine.score"]["seconds"] == pytest.approx(inner.seconds)
    assert s["engine.pass"]["seconds"] == pytest.approx(outer.seconds)
    assert outer.seconds >= inner.seconds + 0.02 >= 0.05
    assert s["engine.pass"]["self_seconds"] == pytest.approx(
        outer.seconds - inner.seconds, abs=1e-6)
    assert 0.02 <= s["engine.pass"]["self_seconds"] < inner.seconds
    assert s["engine.score"]["self_seconds"] == pytest.approx(inner.seconds)


def test_an_undeclared_name_raises():
    reg = telemetry.Registry()
    for make in (reg.span, reg.wait):
        with pytest.raises(ValueError, match="undeclared"):
            make("rank.decodee")
    with pytest.raises(ValueError):
        reg.wait("nope", since_ns=time.perf_counter_ns())
    assert all(v["count"] == 0 for k, v in reg.stats().items() if k != "gc")


def test_every_declared_name_is_present_at_zero():
    s = telemetry.Registry().stats()
    for name in telemetry.NAMES:
        assert s[name] == {"count": 0, "seconds": 0.0, "self_seconds": 0.0}
    assert s["gc"] == {f"gen{i}": {"count": 0, "seconds": 0.0}
                       for i in range(3)}
    served = PredictionService(predictor=HabitatPredictor()).stats()["spans"]
    assert set(telemetry.NAMES) | {"gc"} == set(served)


def test_threads_write_concurrently_with_no_lost_counts():
    reg = telemetry.Registry()
    threads, per_thread = 16, 2000
    go = threading.Barrier(threads)

    def work():
        go.wait()
        for _ in range(per_thread):
            with reg.span("engine.pass"):
                with reg.span("engine.score"):
                    pass
            reg.wait("rank.queue", since_ns=time.perf_counter_ns())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    s = reg.stats()
    for name in ("engine.pass", "engine.score", "rank.queue"):
        assert s[name]["count"] == threads * per_thread
    assert s["engine.pass"]["self_seconds"] <= s["engine.pass"]["seconds"]


# -- the collector ---------------------------------------------------------------
def test_a_collection_inside_a_span_is_counted_and_keeps_the_stack(hooked):
    before = hooked.stats()["gc"]["gen2"]["count"]
    with hooked.span("engine.pass") as outer:
        with hooked.span("engine.score"):
            gc.collect()
        gc.collect()
    s = hooked.stats()
    assert s["gc"]["gen2"]["count"] == before + 2
    paused = s["gc"]["gen2"]["seconds"]
    assert s["gc.pause"]["seconds"] >= paused > 0
    # a pause is a child of the span the thread was in
    assert s["engine.score"]["self_seconds"] < s["engine.score"]["seconds"]
    assert (s["engine.pass"]["self_seconds"]
            <= outer.seconds - s["engine.score"]["seconds"] + 1e-6)
    assert hooked._thread().stack == []
    with hooked.span("engine.pass"):
        pass
    assert hooked.stats()["engine.pass"]["count"] == 2


def test_collections_inside_span_bookkeeping_leave_it_sound(hooked,
                                                            tmp_path):
    """With a collection every few allocations, pauses start inside the
    spans' own enter and exit: counts stay exact, the stack empties, and
    the profiled phases still never overlap."""
    def run():
        old = gc.get_threshold()
        gc.set_threshold(1, 1, 1)
        try:
            for _ in range(2000):
                with hooked.span("rank.decode", req=1):
                    with hooked.span("rank.admit"):
                        [[] for _ in range(3)]
        finally:
            gc.set_threshold(*old)

    events = _program_events(
        profile.load_events(_profiled(tmp_path, run))["host"])
    assert {e[2] for e in events} == {"rank.decode", "rank.admit",
                                      "gc.pause"}
    assert all(a[1] <= b[0] for a, b in zip(events, events[1:]))
    s = hooked.stats()
    assert s["rank.decode"]["count"] == s["rank.admit"]["count"] == 2000
    assert sum(g["count"] for g in s["gc"].values()) > 100
    assert hooked._thread().stack == []
    assert 0 <= s["rank.decode"]["self_seconds"] <= s["rank.decode"]["seconds"]


def test_waits_leave_no_host_event(tmp_path):
    reg = telemetry.Registry()

    def run():
        t0 = time.perf_counter_ns()
        with reg.wait("rank.wait"):
            time.sleep(0.01)
        reg.wait("rank.queue", since_ns=t0)
        with reg.span("rank.encode"):
            time.sleep(0.001)

    path = _profiled(tmp_path, run)
    names = {e[2] for e in profile.load_events(path)["host"]}
    assert "rank.encode" in names
    assert not names & set(WAITS)
    s = reg.stats()
    assert s["rank.wait"]["count"] == s["rank.queue"]["count"] == 1
    assert s["rank.queue"]["seconds"] >= s["rank.wait"]["seconds"] >= 0.01


def test_a_collection_in_a_profile_is_its_own_segment(tmp_path, hooked):
    def run():
        with hooked.span("rank.decode"):
            time.sleep(0.002)
            gc.collect()
            time.sleep(0.002)

    events = _program_events(
        profile.load_events(_profiled(tmp_path, run))["host"])
    assert [e[2] for e in events] == ["rank.decode", "gc.pause",
                                      "rank.decode"]
    assert all(a[1] <= b[0] for a, b in zip(events, events[1:]))


# -- one /rank through the threaded front door ------------------------------------
@pytest.fixture(scope="module")
def server():
    service = PredictionService(predictor=_scoring_predictor(),
                                coalesce_window_ms=0.0)
    srv = PredictionServer(service).start()
    try:
        yield srv
    finally:
        srv.shutdown()


def test_one_rank_moves_each_rank_path_span_by_one(server):
    client = PredictionClient(server.url)
    client.rank(_trace(24), 8)          # compile the scorer's bucket
    trace = _trace(25)                  # tracking moves trace.track
    before = _counts(client.stats()["spans"])
    client.rank(trace, 8)
    after = _counts(client.stats()["spans"])
    moved = {n: after[n] - before[n] for n in telemetry.NAMES
             if n != "gc.pause"}
    assert moved == {n: int(n in RANK_PATH) for n in moved}


def test_a_profiled_rank_has_one_phase_at_a_time(server, tmp_path):
    """Under ``jax.profiler`` one ``/rank``'s annotations are innermost
    phases: none overlaps another, waits leave none, and the request's
    spans carry its id and the batch its engine pass ran in."""
    client = PredictionClient(server.url)
    client.rank(_trace(26), 8)
    trace = _trace(27)                  # tracked outside the profile

    def rank_until_replied():
        # the client can read the answer before the handler has closed
        # its ``http.reply`` span: wait for it inside the profile
        replies = telemetry.stats()["http.reply"]["count"]
        client.rank(trace, 8)
        deadline = time.monotonic() + 10
        while (telemetry.stats()["http.reply"]["count"] == replies
               and time.monotonic() < deadline):
            time.sleep(0.001)

    path = _profiled(tmp_path, rank_until_replied)
    events = [e for e in _program_events(profile.load_events(path)["host"])
              if e[2] != "gc.pause"]     # another thread may collect
    names = [e[2] for e in events]
    assert set(names) == set(RANK_PATH) - set(WAITS)
    assert names.index("engine.score") > names.index("engine.pass")
    assert all(a[1] <= b[0] for a, b in zip(events, events[1:])), events
    from jax.profiler import ProfileData

    meta = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in telemetry.NAMES:
                    meta.setdefault(e.name, []).append(dict(e.stats))
    reqs = {m["req"] for n in ("http.read", "rank.decode", "rank.encode")
            for m in meta[n]}
    assert len(reqs) == 1
    batches = {m["batch"] for n in ("engine.pass", "engine.score",
                                    "rank.encode", "http.reply")
               for m in meta[n]}
    assert len(batches) == 1
