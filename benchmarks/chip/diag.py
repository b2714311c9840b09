"""Where a served request's time went: wall-clock spans of each request's
phases in the server and of every collector pause, in the server and in
the load generator.  ``run.py --diag FILE`` writes them with each
request's due, send and answer times; the benchmark's own runs leave it
off.
"""

from __future__ import annotations

import gc
import threading
import time

SPANS = []                      # (name, thread, start, end), wall clock
PAUSES = []                     # (generation, start, end, collected)
_started = {}


def _timed(cls, name: str) -> None:
    fn = getattr(cls, name)

    def timed(*args, **kwargs):
        t0 = time.time()
        try:
            return fn(*args, **kwargs)
        finally:
            SPANS.append((name, threading.get_ident(), t0, time.time()))

    setattr(cls, name, timed)


def _collector(phase: str, info: dict) -> None:
    if phase == "start":
        _started[threading.get_ident()] = time.time()
    else:
        PAUSES.append((info["generation"],
                       _started.pop(threading.get_ident(), time.time()),
                       time.time(), info["collected"]))


def watch_collector() -> None:
    gc.callbacks.append(_collector)


def install_server() -> None:
    """Time the front end's handler and the service's decode, engine and
    whole-request calls, and the collector."""
    from repro.serve import http
    from repro.serve.service import PredictionService

    _timed(http._Handler, "do_POST")
    for name in ("rank_request", "decode_rank", "rank"):
        _timed(PredictionService, name)
    watch_collector()


def snapshot() -> dict:
    return {"spans": list(SPANS), "pauses": list(PAUSES)}
