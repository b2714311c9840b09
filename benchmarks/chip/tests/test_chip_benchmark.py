"""CPU tests of the chip benchmark: its file, its pieces found by name, the
open loop's timing, the profile reduction, the work counts, the plain
reference against the program, and the checks that must fail.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import http.server
import json
import os
import re
import subprocess
import sys
import threading
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import bench, control, profile, reference, serving, wire, work  # noqa: E402,E501

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def doc():
    return bench.load_benchmark()


# -- the benchmark file --------------------------------------------------------
def test_names_and_units_use_allowed_characters(doc):
    names = ([c["name"] for c in doc["configs"]]
             + [w["name"] for w in doc["workloads"]]
             + [w["config"] for w in doc["workloads"]]
             + [w["traffic"] for w in doc["workloads"]]
             + [m["name"] for g in ("end_to_end", "per_layer") for m in doc[g]]
             + [k for c in doc["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), [n for n in names
                                               if not NAME.match(n)]
    units = [m["unit"] for g in ("end_to_end", "per_layer") for m in doc[g]]
    assert all(UNIT.match(u) for u in units)
    for g in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in doc[g]]
        assert len(seen) == len(set(seen))
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert m["better"] in ("lower", "higher")
    texts = ([w["why"] for w in doc["workloads"]]
             + [c["source"] for c in doc["configs"]]
             + [m["layer"] for m in doc["per_layer"]])
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_keys_and_bounds_follow_the_contract(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    cells = 24
    assert (2 + 14 * cells) * (doc["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for w in doc["workloads"]:
        assert w["chips"] == 1
        reports = bench.metric_spec(doc, "end_to_end", w["name"])
        assert "setup_s" in [m["name"] for m in reports] and len(reports) > 1
        layer = bench.metric_spec(doc, "per_layer", w["name"])
        assert layer
        assert all(m["moves"] in [r["name"] for r in reports] for m in layer)
    layers = {}
    for m in doc["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_piece_of_every_cell_is_found_by_name(doc):
    for c in doc["configs"]:
        assert (ROOT / c["file"]).exists()
        cfg = bench.load_config(c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (HERE / "data" / c["name"] / "index.json").exists()
    for w in doc["workloads"]:
        traffic = bench.load_traffic(w["traffic"])
        gen = bench.generator(traffic["kind"])
        assert (HERE / f"{gen.RUNNER}.py").exists()
        assert (HERE / "limits" / f"{w['name']}.json").exists()
    for m in doc["per_layer"]:
        assert callable(bench.metric_reader(m["name"]).read)
    assert bench.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        bench.peaks("TPU v99")


def test_a_cell_without_the_program_exits_nonzero(tmp_path):
    """A checkout of BENCHMARK.json and the benchmark alone prints no
    result and fails."""
    (tmp_path / "benchmarks").mkdir()
    import shutil

    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    name = bench.load_benchmark()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", name, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- traffic -------------------------------------------------------------------
def _docs(config, n=None):
    docs = bench.corpus(bench.load_config(config))
    return docs[:n] if n else docs


def test_streams_repeat_for_a_seed_and_differ_across_seeds():
    docs = _docs("resnet50")
    cfg = bench.load_config("resnet50")
    tpls = [wire.Template(d["doc"]) for d in docs]
    traffic = bench.load_traffic("rank-cold")
    gen = bench.generator("open_rank")
    big = 2**33 + 12345
    a = gen.make(traffic, cfg, docs, big, 5.0, tpls)
    b = gen.make(traffic, cfg, docs, big, 5.0, tpls)
    c = gen.make(traffic, cfg, docs, big + 1, 5.0, tpls)
    assert [r.body for r in a.requests] == [r.body for r in b.requests]
    assert [r.body for r in a.requests] != [r.body for r in c.requests]
    # the schedule is the rate's and the window's, not the seed's
    assert [r.due for r in a.requests] == [r.due for r in c.requests]
    first = json.loads(a.requests[0].body)
    i, measured = a.requests[0].traces[0]
    assert [op["measured_ms"] for op in first["trace"]["ops"]] == \
        measured.tolist()
    assert first["batch_size"] == a.requests[0].batch == docs[i]["batch"]


def test_rank_hot_draws_from_its_population():
    docs = _docs("resnet50")
    cfg = bench.load_config("resnet50")
    tpls = [wire.Template(d["doc"]) for d in docs]
    traffic = bench.load_traffic("rank-hot")
    s = bench.generator("open_rank").make(traffic, cfg, docs, 7, 10.0, tpls)
    pop = {id(r.traces[0][1]) for r in s.prefill}
    assert len(pop) == len(s.prefill) == traffic["population"]
    cold = [r for r in s.requests if id(r.traces[0][1]) not in pop]
    assert len(cold) == traffic["fresh"]


class _Stalling(http.server.BaseHTTPRequestHandler):
    """Answers at once, except the first request, which stalls 0.5 s."""
    stalled = threading.Event()

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        if not self.stalled.is_set():
            self.stalled.set()
            time.sleep(0.5)
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"{}")

    def log_message(self, *a):
        pass


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stalling)
    threading.Thread(target=srv.serve_forever, daemon=True).start()

    class Stub:
        host, port = srv.server_address

    reqs = [wire.Request(due=0.1 * k, path="/rank", body=b"{}", traces=[])
            for k in range(5)]
    try:
        # one sender: the stall of request 0 delays requests 1-4
        run = serving.open_loop(Stub(), reqs, 0.6, senders=1)
    finally:
        srv.shutdown()
    outs = run["outcomes"]
    assert outs[0].latency_s >= 0.5
    # request 1 was due at 0.1 s and could not be sent before 0.5 s
    assert outs[1].late_s >= 0.35 and outs[1].latency_s >= outs[1].late_s
    s = serving.summarize(reqs, run)
    assert s["lateness_max_ms"] >= 350 and s["succeeded"] == 5
    assert serving.latency_metric("rank_p50_ms", s) >= 250


# -- profile reduction and work counts --------------------------------------------
def test_profile_reduction_of_a_recorded_excerpt():
    """Events recorded on a v5e (``profile_excerpt.json``), reduced."""
    ex = json.loads((Path(__file__).parent / "profile_excerpt.json")
                    .read_text())
    events = {"device": {p: [tuple(e) for e in evs]
                         for p, evs in ex["device"].items()},
              "host": [tuple(e) for e in ex["host"]]}
    prof = profile.reduce_events(events, ex["window_s"])
    want = ex["expected"]
    assert prof["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert profile.kernel_seconds(prof, ex["kernel_pattern"]) == \
        pytest.approx(want["kernel_s"], rel=1e-9)
    assert 1 - prof["busy_s"] / ex["window_s"] == \
        pytest.approx(want["idle_share"], rel=1e-9)
    assert len(prof["breakdown"]["idle_gaps"]) <= 10


def test_profile_reduction_by_hand():
    ev = {"device": {"/device:TPU:0": [(0, 10, "a"), (5, 20, "b"),
                                       (50, 60, "a")]},
          "host": [(15, 55, "decode")]}
    prof = profile.reduce_events(ev, 100e-9)
    assert prof["busy_s"] == pytest.approx(30e-9)
    assert profile.kernel_seconds(prof, "^a$") == pytest.approx(20e-9)
    assert prof["breakdown"]["idle_gaps"] == [["decode", 30e-9]]


def test_latency_reader_takes_its_endpoint_from_the_metric_name():
    """``latency_p95_ms.<endpoint>``: the nearest-rank p95 of that
    endpoint's requests alone, a failed or shed request infinitely late."""
    reader = bench.metric_reader("latency_p95_ms.rank")
    reqs = [wire.Request(due=0.0, path=p, body=b"", traces=[])
            for p in ["/rank"] * 20 + ["/sweep"] * 5]
    outs = [serving.Outcome(200, b"", 0.0, (k + 1) / 1e3)
            for k in range(19)] + [serving.Outcome(503, b"", 0.0, 0.0)]
    outs += [serving.Outcome(200, b"", 0.0, 9.0)] * 5
    ctx = {"requests": reqs, "outcomes": outs}
    assert reader.read(dict(ctx, metric="latency_p95_ms.rank")) == 19.0
    outs[10] = serving.Outcome(0, b"", 0.0, 0.0)
    assert reader.read(dict(ctx, metric="latency_p95_ms.rank")) == float("inf")
    assert reader.read(dict(ctx, metric="latency_p95_ms.sweep")) == 9000.0
    assert reader.read(dict(ctx, metric="latency_p95_ms.optimize")) is None
    assert reader.read({"metric": "latency_p95_ms.rank"}) is None


def test_work_count_of_a_known_mlp_shape():
    shapes = work.layer_shapes(13, 256, 3)
    assert shapes == [(13, 256), (256, 256), (256, 256), (256, 1)]
    assert work.flops_per_row(shapes) == 2 * (13 * 256 + 2 * 256 * 256 + 256)
    flops, nbytes = work.mlp_work(1000, 2, 2, shapes)
    assert flops == 1000 * 269312
    wb = 4 * (13 * 256 + 256 + 2 * (256 * 256 + 256) + 256 + 1)
    assert nbytes == 1000 * 14 * 4 + 2 * 2 * wb


# -- the plain reference ----------------------------------------------------------
def _program_planner(mlps, scorer="jnp"):
    import jax.numpy as jnp
    from repro.core import mlp as mlp_mod
    from repro.core.predictor import HabitatPredictor
    from repro.serve.fleet import FleetPlanner

    trained = {k: mlp_mod.TrainedMLP(
        kind=k, cfg=mlp_mod.MLPConfig(in_features=13, hidden_layers=3,
                                      hidden_size=256),
        params=[(jnp.asarray(w), jnp.asarray(b))
                for w, b in zip(m["w"], m["b"])],
        feature_mean=m["mean"], feature_std=m["std"])
        for k, m in mlps.items()}
    return FleetPlanner(predictor=HabitatPredictor(mlps=trained,
                                                   sweep_scorer=scorer))


def test_reference_agrees_with_the_program_on_a_resnet50_trace():
    from repro.core.trace import TrackedTrace

    docs = _docs("resnet50")
    rdocs = [reference.Doc(d["doc"]) for d in docs]
    devs = reference.device_table()
    mlps = reference.make_mlps(5, reference.feature_stats(rdocs, devs))
    planner = _program_planner(mlps)
    rng = np.random.default_rng(0)
    for i in (0, 10, 20):
        tpl = wire.Template(docs[i]["doc"])
        measured = wire.jittered(tpl.measured, rng, 0.1)
        trace = TrackedTrace.from_json(tpl.render(measured))
        got = planner.sweep([trace])[0]
        p = reference.Predictor(rdocs[i], mlps, devs)
        ref = p.iter_ms(measured)
        rel = np.abs(np.array([got[d["name"]] for d in devs]) - ref) / p.mlp_ms
        assert rel.max() < 1e-5
        assert p.mlp_ms.min() > 0 and np.all(ref > p.mlp_ms)


def _served(planner, docs, i, measured, by):
    """A /rank request for corpus trace ``i`` and the program's answer to
    it, as the wire carries both."""
    from repro.core.trace import TrackedTrace
    from repro.serve.service import PredictionService

    tpl = wire.Template(docs[i]["doc"])
    trace = TrackedTrace.from_json(tpl.render(measured))
    batch = docs[i]["batch"]
    ans = PredictionService.encode_rank(trace, planner.rank(trace, batch,
                                                            by=by))
    req = wire.Request(due=0.0, path="/rank", body=b"", batch=batch, by=by,
                       traces=[(i, measured)])
    return req, serving.Outcome(200, json.dumps(ans).encode(), 0.0, 0.1)


def test_the_answer_check_reads_order_and_rows():
    """The program's rankings pass; a reversed order, a swapped objective
    or a wrong derived number in a row is caught."""
    docs = _docs("resnet50")
    rdocs = [reference.Doc(d["doc"]) for d in docs]
    devs = reference.device_table()
    mlps = reference.make_mlps(9, reference.feature_stats(rdocs, devs))
    planner = _program_planner(mlps)
    rng = np.random.default_rng(1)
    preds = {i: reference.Predictor(rdocs[i], mlps, devs) for i in (3, 17)}
    pairs = [_served(planner, docs, i, wire.jittered(
        rdocs[i].measured, rng, 0.1), by)
        for i, by in ((3, "throughput"), (17, "cost"))]
    reqs, outs = [r for r, _ in pairs], [o for _, o in pairs]
    ok = serving.check_answers(reqs, outs, preds)
    assert ok["answer_gap"] < 1e-5
    assert ok["wrong_shape"] == ok["wrong_order"] == ok["wrong_rows"] == 0

    def altered(change):
        out = []
        for o in outs:
            ans = json.loads(o.body)
            change(ans)
            out.append(serving.Outcome(200, json.dumps(ans).encode(),
                                       0.0, 0.1))
        return serving.check_answers(reqs, out, preds)

    assert altered(lambda a: a["ranking"].reverse())["wrong_order"] == 2
    swapped = [wire.Request(**{**r.__dict__, "by": {"throughput": "cost",
                                                    "cost": "throughput"}[
                                                        r.by]})
               for r in reqs]
    assert serving.check_answers(swapped, outs, preds)["wrong_order"] == 2

    def bad_row(a):
        a["ranking"][0]["throughput"] *= 1.0001
    assert altered(bad_row)["wrong_rows"] == 2


# -- the checks that must fail ------------------------------------------------------
@pytest.mark.parametrize("workload", ["resnet50.rank-cold",
                                      "resnet50.rank-hot"])
def test_the_control_fails_the_answer_gap_limit(workload):
    cell = bench.cell(bench.load_benchmark(), workload)
    docs = [reference.Doc(d["doc"])
            for d in _docs(bench.load_config(cell["config"])["name"])]
    devs = reference.device_table()
    stats = reference.feature_stats(docs, devs)
    limit = json.loads((HERE / "limits" / f"{workload}.json")
                       .read_text())["answer_gap"]
    for seed in (1, 2, 3):
        gap = control.control_gap(docs[::5], reference.make_mlps(seed, stats),
                                  devs)
        assert gap > limit


@pytest.mark.parametrize("fault,check", [("answer", "answer_gap"),
                                         ("control", "answer_gap"),
                                         ("order", "wrong_order")])
def test_a_run_with_a_wrong_answer_where_it_is_produced_is_not_correct(
        fault, check):
    """The whole run on the CPU, the chip check lifted, with a fault
    planted in the timed path: every scored time off by a thousandth,
    the control's lower-precision forward in the scorer's place, or each
    ranking reversed.  ``correct`` comes out false on the number that
    should catch it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "resnet50.rank-cold", "--seed", "2147483999", "--seconds", "2",
         "--trace", "0", "--any-platform", "--fault", fault],
        cwd=ROOT, capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


def test_a_sound_run_is_correct_and_diag_times_every_request(tmp_path):
    """The whole run on the CPU with nothing planted reads ``correct``,
    and ``--diag`` records the server's handler span of every request."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out_file = tmp_path / "diag.json"
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "resnet50.rank-cold", "--seed", "2147484001", "--seconds", "2",
         "--trace", "0", "--any-platform", "--diag", str(out_file)],
        cwd=ROOT, capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        m["name"] for m in bench.metric_spec(
            bench.load_benchmark(), "end_to_end", "resnet50.rank-cold")}
    d = json.loads(out_file.read_text())
    assert len(d["requests"]) == out["attempted"]
    w0, w1 = d["window"]
    posts = [s for s in d["server"]["spans"]
             if s[0] == "do_POST" and s[2] >= w0]
    assert len(posts) == out["attempted"]
    assert all(due <= sent <= done for due, sent, done, _, _ in d["requests"])
