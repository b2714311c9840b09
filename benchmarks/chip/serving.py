"""Runs a serving cell: the launcher in a child process, open-loop load
from this one, the answers checked against the plain reference.

This process never touches a chip: the child alone holds it.  Each
request is timed from when it was due, not from when a sender thread got
to it, so a stall is charged to every request it delays; how late the
senders ran is reported apart.
"""

from __future__ import annotations

import http.client
import json
import math
import os
import queue
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import bench, reference, wire

HERE = bench.HERE
SENDERS = 64                    #: sender threads of the open loop
DRAIN_S = 60.0                  #: wait for answers past the window's close
READY_S = 900.0                 #: the server's start-up, first run included


class Server:
    """The program's launcher in a child process (``serve_child.py``)."""

    def __init__(self, run_dir: Path, env: dict, require_tpu: bool = True,
                 fault: Optional[str] = None, diag: bool = False):
        self.dir = run_dir
        own = ["--run-dir", str(run_dir)]
        if not require_tpu:
            own += ["--any-platform", "--scorer", "jnp"]
        if fault:
            own += ["--fault", fault]
        if diag:
            own += ["--diag"]
        cmd = [sys.executable, str(HERE / "serve_child.py"), *own, "--",
               "--serve", "--fleet-mlps", "--host", "127.0.0.1",
               "--port", "0"]
        self.out = open(run_dir / "server.out", "w")
        self.err = open(run_dir / "server.err", "w")
        self.proc = subprocess.Popen(cmd, env=env, stdout=self.out,
                                     stderr=self.err, cwd=bench.ROOT,
                                     start_new_session=True)
        self.url = self._wait_ready()
        host, port = self.url.split("//", 1)[1].rsplit(":", 1)
        self.host, self.port = host, int(port)

    def _wait_ready(self) -> str:
        deadline = time.monotonic() + READY_S
        while time.monotonic() < deadline:
            text = (self.dir / "server.out").read_text()
            m = re.search(r"^serving on (\S+)$", text, re.M)
            if m:
                return m.group(1)
            if self.proc.poll() is not None:
                self.fail(f"the server exited with {self.proc.returncode} "
                          f"before it was ready")
            time.sleep(0.05)
        self.fail("the server was not ready in time")

    def fail(self, why: str):
        err = (self.dir / "server.err").read_text()[-4000:]
        self.stop()
        bench.say(err)
        raise SystemExit(f"serving: {why}")

    def command(self, cmd: str, spec: Optional[dict] = None,
                timeout: float = 600.0) -> dict:
        """Drop a command file for the child's control thread and wait for
        its answer."""
        tmp = self.dir / f".{cmd}"
        tmp.write_text(json.dumps(spec or {}))
        os.replace(tmp, self.dir / cmd)
        done = self.dir / f"{cmd}.done"
        deadline = time.monotonic() + timeout
        while not done.exists():
            if self.proc.poll() is not None:
                self.fail(f"the server exited during {cmd!r}")
            if time.monotonic() > deadline:
                self.fail(f"no answer to {cmd!r}")
            time.sleep(0.005)
        out = json.loads(done.read_text())
        done.unlink()
        return out

    def get(self, path: str) -> dict:
        conn = http.client.HTTPConnection(self.host, self.port, timeout=60)
        try:
            conn.request("GET", path)
            return json.loads(conn.getresponse().read())
        finally:
            conn.close()

    def stop(self) -> None:
        """SIGTERM (the launcher drains), then SIGKILL the whole group."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self.out.close()
        self.err.close()


def post(host: str, port: int, path: str, body: bytes):
    """(status, body) of one POST; status 0 when the transport failed."""
    conn = http.client.HTTPConnection(host, port, timeout=DRAIN_S + 60)
    try:
        conn.request("POST", path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    except OSError as e:
        return 0, repr(e).encode()
    finally:
        conn.close()


class Outcome:
    """What became of one request: the seconds from its due time to its
    send and to its answer, and the answer."""
    __slots__ = ("status", "body", "late_s", "latency_s")

    def __init__(self, status, body, late_s, latency_s):
        self.status, self.body = status, body
        self.late_s, self.latency_s = late_s, latency_s


def open_loop(server: Server, requests: List[wire.Request],
              seconds: float, senders: int = SENDERS) -> dict:
    """Send ``requests`` at their due times from ``senders`` threads;
    wait for the answers until ``DRAIN_S`` past the window's close."""
    out: List[Optional[Outcome]] = [None] * len(requests)
    work: "queue.Queue[Optional[int]]" = queue.Queue()
    t0 = time.perf_counter() + 0.01
    wall_t0 = time.time() + (t0 - time.perf_counter())

    def sender():
        while True:
            i = work.get()
            if i is None:
                return
            r = requests[i]
            due = t0 + r.due
            sent = time.perf_counter()
            status, body = post(server.host, server.port, r.path, r.body)
            done = time.perf_counter()
            out[i] = Outcome(status, body, sent - due, done - due)

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(senders)]
    for t in threads:
        t.start()
    for i, r in enumerate(requests):
        delay = t0 + r.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        work.put(i)
    close = t0 + seconds
    time.sleep(max(0.0, close - time.perf_counter()))
    backlog = sum(o is None for o in out)
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(timeout=max(0.0, close + DRAIN_S - time.perf_counter()))
    return {"outcomes": out, "backlog_at_close": backlog,
            "t0": t0, "wall_t0": wall_t0, "wall_closed": time.time()}


def summarize(requests: List[wire.Request], run: dict) -> dict:
    """Counts, latency quantiles and lateness of one open-loop run."""
    outs = run["outcomes"]
    ok = [o for o in outs if o is not None and o.status == 200]
    shed = [o for o in outs if o is not None and o.status in (429, 503, 504)]
    lat = [o.latency_s * 1e3 if o is not None and o.status == 200
           else float("inf") for o in outs]
    late = [o.late_s * 1e3 for o in outs if o is not None]
    by_path: Dict[str, List[float]] = {}
    for r, v in zip(requests, lat):
        by_path.setdefault(r.path.strip("/"), []).append(v)
    return {"sent": len(outs), "succeeded": len(ok), "shed": len(shed),
            "failed": len(outs) - len(ok),
            "unanswered": sum(o is None for o in outs),
            "backlog_at_close": run["backlog_at_close"],
            "lateness_p50_ms": bench.nearest_rank(late, 0.5),
            "lateness_max_ms": max(late) if late else float("nan"),
            "latency_ms": by_path}


def latency_metric(name: str, summary: dict) -> Optional[float]:
    """``<endpoint>_p<q>_ms``: the q-th percentile of that endpoint's
    latency over all requests of the window, failures as missing."""
    m = re.fullmatch(r"([a-z]+)_p(\d+)_ms", name)
    if not m or m.group(1) not in summary["latency_ms"]:
        return None
    return bench.nearest_rank(summary["latency_ms"][m.group(1)],
                              int(m.group(2)) / 100.0)


# -- correctness -------------------------------------------------------------
#: reference ranking keys closer than this (relative) are a tie that a
#: sound answer may order either way: five times the ``answer_gap``
#: limit; adjacent keys of the corpus lie 2.4e-4 apart or more
TIE = 1e-5
#: a row's derived numbers against the same arithmetic on its iteration
#: time: float64 rounding of sums over a few thousand ops, no more
ROW_RTOL = 1e-9


def ranking_key(by: str, batch: int, iter_ms: float, price) -> float:
    """What a ranking sorts on, best first: samples/s, or samples per
    dollar (an unpriced device 0, a free one infinite)."""
    tput = batch / (iter_ms * 1e-3)
    if by != "cost":
        return tput
    if price is None:
        return 0.0
    return math.inf if price == 0 else tput / (price / 3600.0)


def _close(got, want) -> bool:
    """A served number against ``want``; the wire spells infinity as the
    string "Infinity" and a missing number as null."""
    if want is None:
        return got is None
    if math.isinf(want):
        return got == "Infinity"
    return (isinstance(got, (int, float))
            and abs(got - want) <= ROW_RTOL * abs(want))


def _rows_agree(ranking: List[dict], batch: int, origin_ms: float,
                prices: Dict[str, Optional[float]]) -> bool:
    """A ranking's throughput, price, samples per dollar and speedup are
    what its own iteration times give."""
    for c in ranking:
        ms, price = c["iter_ms"], prices[c["device"]]
        tput = batch / (ms * 1e-3)
        if not (_close(c["throughput"], tput)
                and c["cost_per_hour"] == price
                and _close(c["cost_normalized"],
                           None if price is None
                           else ranking_key("cost", batch, ms, price))
                and _close(c["speedup_vs_origin"], origin_ms / ms)):
            return False
    return True


def _in_order(devices: List[str], keys: Dict[str, float]) -> bool:
    """``devices`` best first by the reference's ``keys``, names breaking
    exact ties."""
    for a, b in zip(devices, devices[1:]):
        ka, kb = keys[a], keys[b]
        if kb > ka * (1 + TIE) or (ka == kb and a > b):
            return False
    return True


def check_answers(requests: List[wire.Request], outcomes, predictors) -> dict:
    """Every answered ``/rank`` request against the plain reference.

    ``answer_gap``: the widest gap between a served iteration time and the
    reference's, over every device of every answer, as a share of the
    reference's MLP-priced part of that cell.  ``wrong_shape``: answers
    whose devices or label differ.  ``wrong_order``:
    rankings not best first by the request's ``by`` on the reference's
    times.  ``wrong_rows``: rankings whose throughput, price, samples per
    dollar or speedup over the trace's own time are not what the row's
    iteration time gives.  ``errors``: requests that got neither an
    answer nor a shed."""
    table = reference.device_table()
    devs = [d["name"] for d in table]
    prices = {d["name"]: d["cost_per_hour"] for d in table}
    gap, wrong, order, rows_bad, errors = 0.0, 0, 0, 0, 0
    for r, o in zip(requests, outcomes):
        if o is None or o.status not in (200, 429, 503, 504):
            errors += 1
            continue
        if o.status != 200:
            continue
        ans = json.loads(o.body)
        row = {c["device"]: c["iter_ms"] for c in ans["ranking"]}
        (i, measured), = r.traces
        p = predictors[i]
        if sorted(row) != devs or ans["label"] != p.doc.label:
            wrong += 1
            continue
        ref = p.iter_ms(measured)
        got = np.array([row[d] for d in devs], np.float64)
        gap = max(gap, float(np.max(np.abs(got - ref) / p.mlp_ms)))
        keys = {d: ranking_key(r.by, r.batch, float(ms), prices[d])
                for d, ms in zip(devs, ref)}
        order += not _in_order([c["device"] for c in ans["ranking"]], keys)
        origin_ms = math.fsum(measured * p.doc.mult)
        rows_bad += not _rows_agree(ans["ranking"], r.batch, origin_ms,
                                    prices)
    return {"answer_gap": gap, "wrong_shape": wrong, "wrong_order": order,
            "wrong_rows": rows_bad, "errors": errors}


# -- the run -----------------------------------------------------------------
def measure(args, server: Server, stream: wire.Stream, traffic: dict,
            started: float) -> Optional[dict]:
    """Set-up on a ready server (scorer buckets, set-up requests, a
    warm-up burst), then the window: everything the run reads from the
    program, or None after a knee sweep."""
    warm = server.command("warm", {"blocks": stream.warm_blocks})
    bench.say(f"set-up: server up, scorer warmed at "
              f"{len(stream.warm_blocks)} block counts in "
              f"{warm['seconds']:.2f} s")
    if stream.prefill:
        pre = open_loop(server, stream.prefill, 0.0, senders=4)
        if any(o is None or o.status != 200 for o in pre["outcomes"]):
            server.fail("a set-up request failed")
    if stream.warmup:
        w = summarize(stream.warmup, open_loop(
            server, stream.warmup, traffic["warmup_s"]))
        w.pop("latency_ms")
        bench.say(f"warm-up: {json.dumps(w)}")
    if args.rates:
        return None
    out = {"setup_s": time.perf_counter() - started,
           "before": server.get("/stats")}
    if args.trace:
        prof_t0 = server.command("profile_start")["t"]
    out["window"] = open_loop(server, stream.requests, float(args.seconds))
    if args.trace:
        out["profile_s"] = (server.command("profile_stop", timeout=300.0)["t"]
                            - prof_t0)
    out["after"] = server.get("/stats")
    out["report"] = server.command("report")
    return out


def run(args, bench_doc: dict, cell: dict, config: dict, traffic: dict,
        gen, started: float, child_env: dict) -> None:
    limits = json.loads((HERE / "limits" / f"{cell['name']}.json")
                        .read_text())
    docs = bench.corpus(config)
    rdocs = [reference.Doc(d["doc"]) for d in docs]
    templates = [wire.Template(d["doc"]) for d in docs]
    devs = reference.device_table()
    mlps = reference.make_mlps(args.seed,
                               reference.feature_stats(rdocs, devs))
    if args.rates:
        traffic = dict(traffic, rate_per_s=max(args.rates))
    stream = gen.make(traffic, config, docs, args.seed, float(args.seconds),
                      templates)
    if args.diag:
        from benchmarks.chip import diag

        diag.watch_collector()
    run_dir = Path(tempfile.mkdtemp(prefix="chipbench-"))
    try:
        reference.save_mlps(run_dir / "mlps.npz", mlps)
        server = Server(run_dir, child_env,
                        require_tpu=not args.any_platform, fault=args.fault,
                        diag=bool(args.diag))
        try:
            got = measure(args, server, stream, traffic, started)
            if got is None:
                sweep_rates(server, gen, traffic, config, docs, args.seed,
                            float(args.seconds), templates, args.rates)
                return
        finally:
            server.stop()
        device = json.loads((run_dir / "device.json").read_text())
        prof = None
        if args.trace:
            from benchmarks.chip import profile

            prof = profile.reduce(run_dir / "profile", got["profile_s"])
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)

    window = got["window"]
    if args.diag:
        write_diag(args.diag, stream.requests, window, got["report"])
    summary = summarize(stream.requests, window)
    bench.say("window: " + json.dumps(
        {k: v for k, v in summary.items() if k != "latency_ms"}))
    report = got["report"]
    in_window = [s for t, s in report["compiles"]
                 if window["wall_t0"] <= t <= window["wall_closed"]]
    bench.say(f"programs made ready: {len(report['compiles'])} in the "
              f"server's life, {len(report['cache_hits'])} of them from the "
              f"persistent cache; {len(in_window)} in the window "
              f"({sum(in_window):.3f} s)")
    t_ref = time.perf_counter()
    predictors = {i: reference.Predictor(rdocs[i], mlps, devs)
                  for i in sorted({i for r in stream.requests
                                   for i, _ in r.traces})}
    verdict = check_answers(stream.requests, window["outcomes"], predictors)
    bench.say(f"reference check of {summary['succeeded']} answers in "
              f"{time.perf_counter() - t_ref:.2f} s")
    answered = summary["succeeded"] + summary["shed"]
    checks = [
        {"name": "answer_gap", "value": verdict["answer_gap"],
         "limit": limits["answer_gap"]},
        *({"name": k, "value": verdict[k], "limit": 0}
          for k in ("wrong_shape", "wrong_order", "wrong_rows", "errors")),
        {"name": "answered", "value": answered, "limit": summary["sent"]},
    ]
    correct = (verdict["answer_gap"] <= limits["answer_gap"]
               and all(verdict[k] == 0 for k in ("wrong_shape", "wrong_order",
                                                 "wrong_rows", "errors"))
               and answered == summary["sent"])

    device["memory_peak_bytes"] = got["report"]["memory_peak_bytes"]
    metrics: Dict[str, tuple] = {}
    if prof is None:
        for m in bench.metric_spec(bench_doc, "end_to_end", cell["name"]):
            v = (got["setup_s"] if m["name"] == "setup_s"
                 else latency_metric(m["name"], summary))
            if v is None:
                raise SystemExit(f"{cell['name']} cannot report {m['name']}")
            metrics[m["name"]] = (v, m["unit"])
    else:
        ctx = {"stats_before": got["before"], "stats_after": got["after"],
               "profile": prof,
               "peaks": (None if args.any_platform
                         else bench.peaks(device["kind"])),
               "requests": stream.requests, "outcomes": window["outcomes"],
               "rdocs": rdocs, "devs": devs, "window_s": got["profile_s"]}
        for m in bench.metric_spec(bench_doc, "per_layer", cell["name"]):
            reader = bench.metric_reader(m["name"])
            v = reader.read(dict(ctx, metric=m["name"]))
            if v is not None:
                metrics[m["name"]] = (v, m["unit"])
        device["busy_s"] = prof["busy_s"]
        device["window_s"] = got["profile_s"]
    bench.result_line(correct=correct, attempted=summary["sent"],
                      failed=summary["failed"], metrics=metrics,
                      device=device, checks=checks,
                      breakdown=prof["breakdown"] if prof else None)


def write_diag(path: str, requests: List[wire.Request], window: dict,
               report: dict) -> None:
    """Each request's due, send and answer times (wall clock) and status,
    with the server's spans and both processes' collector pauses."""
    from benchmarks.chip import diag

    rows = [[window["wall_t0"] + r.due,
             None if o is None else window["wall_t0"] + r.due + o.late_s,
             None if o is None else window["wall_t0"] + r.due + o.latency_s,
             None if o is None else o.status, len(r.body)]
            for r, o in zip(requests, window["outcomes"])]
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps({
        "requests": rows, "window": [window["wall_t0"],
                                     window["wall_closed"]],
        "server": report["diag"], "generator": diag.snapshot()}))


def sweep_rates(server, gen, traffic, config, docs, seed, seconds,
                templates, rates) -> None:
    """The knee sweep: windows at each offered rate on one server, each
    with its own requests; prints p50, p95, lateness and backlog."""
    for k, rate in enumerate(rates):
        stream = gen.make(dict(traffic, rate_per_s=rate), config, docs,
                          seed + 1000 * (k + 1), seconds, templates)
        s = summarize(stream.requests,
                      open_loop(server, stream.requests, seconds))
        lat = next(iter(s["latency_ms"].values()))
        print(json.dumps({"rate_per_s": rate, "sent": s["sent"],
                          "succeeded": s["succeeded"], "shed": s["shed"],
                          "p50_ms": bench.nearest_rank(lat, 0.5),
                          "p95_ms": bench.nearest_rank(lat, 0.95),
                          "lateness_p50_ms": s["lateness_p50_ms"],
                          "backlog_at_close": s["backlog_at_close"]}),
              flush=True)
