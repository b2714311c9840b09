"""The readers of the program's span totals, on a recorded ``/stats`` pair.

``span_stats_pair.json`` holds the ``spans`` blocks of ``/stats`` before
and after four ``/rank`` requests (one a repeat the result cache
answered) to a CPU server, and the seconds between them.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import bench  # noqa: E402

PAIR = json.loads((Path(__file__).parent / "span_stats_pair.json")
                  .read_text())
SPAN_METRICS = ("front_door_ms.rank", "decode_ms.rank", "queue_wait_ms.rank",
                "engine_host_ms.rank", "scorer_call_ms.rank",
                "gc_pause_pct.rank")


def _d(name, field="seconds"):
    a = PAIR["stats_before"]["spans"][name]
    b = PAIR["stats_after"]["spans"][name]
    return b[field] - a[field]


def _read(metric, ctx=None):
    ctx = dict(PAIR if ctx is None else ctx, metric=metric)
    return bench.metric_reader(metric).read(ctx)


def test_the_recorded_pair_moved_every_span_the_readers_divide_by():
    for name in ("http.read", "rank.decode", "rank.queue", "engine.pass",
                 "engine.score"):
        assert _d(name, "count") > 0


@pytest.mark.parametrize("metric,want", [
    ("front_door_ms.rank",
     lambda: 1e3 * (_d("http.read") + _d("http.reply"))
     / _d("http.read", "count")),
    ("decode_ms.rank",
     lambda: 1e3 * _d("rank.decode") / _d("rank.decode", "count")),
    ("queue_wait_ms.rank",
     lambda: 1e3 * _d("rank.queue") / _d("rank.queue", "count")),
    ("engine_host_ms.rank",
     lambda: 1e3 * _d("engine.pass", "self_seconds")
     / _d("engine.pass", "count")),
    ("scorer_call_ms.rank",
     lambda: 1e3 * _d("engine.score") / _d("engine.score", "count")),
    ("gc_pause_pct.rank",
     lambda: 100.0 * sum(
         PAIR["stats_after"]["spans"]["gc"][g]["seconds"]
         - PAIR["stats_before"]["spans"]["gc"][g]["seconds"]
         for g in ("gen0", "gen1", "gen2")) / PAIR["window_s"]),
])
def test_each_reader_of_the_recorded_pair(metric, want):
    got = _read(metric)
    assert got == pytest.approx(want(), rel=1e-12)
    assert got > 0


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_window_with_nothing_recorded_reads_none(metric):
    """Stats that did not move give no number: every count, and the
    window of the collector's share, is the denominator."""
    still = {"stats_before": PAIR["stats_before"],
             "stats_after": copy.deepcopy(PAIR["stats_before"]),
             "window_s": PAIR["window_s"]}
    if metric.startswith("gc_pause_pct"):
        still = dict(PAIR, window_s=0.0)
    assert _read(metric, still) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_program_without_spans_reads_none(metric):
    """A server whose ``/stats`` has no ``spans`` block (before this
    program recorded any), or an untraced run, gives no number."""
    bare = {"stats_before": {"requests": {}}, "stats_after": {"requests": {}},
            "window_s": 50.0}
    assert _read(metric, bare) is None
    assert _read(metric, {"window_s": 50.0}) is None


def test_the_new_metrics_are_declared_as_the_readers_read_them():
    doc = bench.load_benchmark()
    spec = {m["name"]: m for m in doc["per_layer"]}
    for name in SPAN_METRICS:
        m = spec[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "rank_p50_ms"
        assert "resnet50.rank-cold" in m["workloads"]
    assert spec["scorer_call_ms.rank"]["workloads"] == ["resnet50.rank-cold"]
