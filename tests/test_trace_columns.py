"""The column-wise trace decode against the per-op one.

``TrackedTrace.from_dict`` decodes a sound document column by column
straight into the trace's arrays and fingerprint, and builds its ``Op``
objects only when ``ops`` is read; anything else falls back to the
per-op decode (span ``trace.decode_slow``).  Both must give the same
trace, bitwise, and malformed input the same error."""

import copy
import gzip
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import OperationTracker
from repro.core.trace import (Op, TraceValidationError, TrackedTrace,
                              _decode_columns, _decode_per_op)
from repro.serve.http import PredictionClient, PredictionServer
from repro.serve.service import PredictionService

ROOT = Path(__file__).resolve().parents[1]
CORPUS = sorted((ROOT / "benchmarks/chip/data/resnet50").glob("*.json.gz"))
GOLDEN = sorted((ROOT / "tests/golden").glob("*.json"))
ARRAY_FIELDS = ("flops", "bytes_accessed", "intensity", "measured_ms",
                "multiplicity", "kernel_varying", "kind_ids", "op_features")


def _count(name):
    return telemetry.stats()[name]["count"]


def _tracked(width=24, label="columns"):
    """A small measured trace with a linear op and elementwise ops."""
    return OperationTracker("T4").track(
        lambda w, x: jnp.sum(jnp.tanh(x @ w)),
        jnp.zeros((8, width)), jnp.zeros((8, 8)), label=label)


@pytest.fixture(scope="module")
def small_doc():
    return _tracked().to_dict()


def _variant(doc, which):
    """Sound documents the corpus does not hold: predicted times, an
    unmeasured op, a multiplicity spelled as a float."""
    doc = copy.deepcopy(doc)
    for i, op in enumerate(doc["ops"]):
        if which == "predicted" and i % 2:
            op["predicted_ms"] = op["measured_ms"] * 2.5
        if which == "float_multiplicity":
            op["multiplicity"] = float(op["multiplicity"] + i % 3)
    if which == "unmeasured":
        doc["ops"][0]["measured_ms"] = None
    return doc


def _documents():
    for path in CORPUS:
        with gzip.open(path) as f:
            yield pytest.param(json.load(f), None, id=path.name)
    for path in GOLDEN:
        blob = json.loads(path.read_text())
        yield pytest.param(blob["trace"], blob["fingerprint"], id=path.stem)
    for which in ("predicted", "unmeasured", "float_multiplicity"):
        yield pytest.param(which, None, id=which)


def _run_time(trace):
    try:
        return trace.run_time_ms
    except ValueError as e:
        return str(e)


def test_the_corpus_is_all_there():
    assert len(CORPUS) == 21 and len(GOLDEN) == 3


@pytest.mark.parametrize("doc,stored_fp", list(_documents()))
def test_column_decode_equals_the_per_op_decode(doc, stored_fp, small_doc):
    if isinstance(doc, str):
        doc = _variant(small_doc, doc)
    built = _count("trace.ops_built")
    fast, slow = _decode_columns(doc), _decode_per_op(doc)
    assert fast is not None
    assert fast.fingerprint() == slow.fingerprint()
    if stored_fp is not None:
        assert fast.fingerprint() == stored_fp
    a, b = fast.to_arrays(), slow.to_arrays()
    for name in ARRAY_FIELDS:
        x, y = getattr(a, name), getattr(b, name)
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert np.array_equal(x, y, equal_nan=True), name
        assert x.tobytes() == y.tobytes(), name
    assert a.kinds == b.kinds
    assert _run_time(fast) == _run_time(slow)
    assert _count("trace.ops_built") == built       # nothing built yet
    assert fast.ops == [Op.from_dict(o) for o in doc["ops"]]
    assert _count("trace.ops_built") == built + 1
    assert fast.to_dict() == slow.to_dict()
    assert fast == slow
    assert _run_time(fast) == _run_time(slow)


def test_a_built_trace_measures_and_refreshes_like_a_per_op_one(small_doc):
    fast = TrackedTrace.from_dict(copy.deepcopy(small_doc))
    slow = _decode_per_op(copy.deepcopy(small_doc))
    assert fast.to_arrays(refresh=True).fingerprint() == \
        slow.to_arrays(refresh=True).fingerprint()
    fp, run_ms = fast.fingerprint(), fast.run_time_ms
    for t in (fast, slow):
        t.ops[0].measured_ms *= 2
        t.to_arrays(refresh=True)
    assert fast.fingerprint() == slow.fingerprint() != fp
    assert fast.run_time_ms == slow.run_time_ms != run_ms
    for t in (fast, slow):
        t.measure()                     # the simulator's times again
    assert fast.fingerprint() == slow.fingerprint() == fp
    assert fast.run_time_ms == slow.run_time_ms == run_ms


def _malformed(doc, case, monkeypatch):
    doc = copy.deepcopy(doc)
    ops = doc["ops"]
    linear = next(o for o in ops if o["kind"] == "linear")
    if case == "missing_field":
        del ops[1]["dtype"]
    elif case == "bool_number":
        ops[0]["cost"]["flops"] = True
    elif case == "numeric_string":
        ops[2]["measured_ms"] = "1.5"
    elif case == "nan":
        ops[0]["measured_ms"] = float("nan")
    elif case == "negative_time":
        ops[1]["measured_ms"] = -1.0
    elif case == "fractional_multiplicity":
        ops[0]["multiplicity"] = 1.5
    elif case == "non_list_shape":
        ops[0]["in_shapes"] = [5]
    elif case == "bool_feature_param":
        linear["params"]["batch"] = True
    elif case == "non_dict_op":
        ops[1] = [1, 2]
    elif case == "int_past_float":
        ops[0]["cost"]["flops"] = 10 ** 400
    elif case == "over_op_cap":
        monkeypatch.setenv("REPRO_TRACE_MAX_OPS", str(len(ops) - 1))
    return doc


@pytest.mark.parametrize("case", [
    "missing_field", "bool_number", "numeric_string", "nan",
    "negative_time", "fractional_multiplicity", "non_list_shape",
    "bool_feature_param", "non_dict_op", "int_past_float", "over_op_cap"])
def test_malformed_documents_fall_back_and_raise_as_before(
        case, small_doc, monkeypatch):
    doc = _malformed(small_doc, case, monkeypatch)
    with pytest.raises(TraceValidationError) as per_op:
        _decode_per_op(doc)
    slow = _count("trace.decode_slow")
    with pytest.raises(TraceValidationError) as served:
        TrackedTrace.from_dict(doc)
    assert type(served.value) is type(per_op.value)
    assert str(served.value) == str(per_op.value)
    assert _count("trace.decode_slow") == slow + 1


def test_numpy_scalars_decode_through_the_fallback(small_doc):
    doc = copy.deepcopy(small_doc)
    for op in doc["ops"]:
        op["multiplicity"] = np.int64(op["multiplicity"])
        op["measured_ms"] = np.float64(op["measured_ms"])
        op["cost"]["flops"] = np.float64(op["cost"]["flops"])
    slow = _count("trace.decode_slow")
    trace = TrackedTrace.from_dict(doc)
    assert _count("trace.decode_slow") == slow + 1
    assert trace.fingerprint() == TrackedTrace.from_dict(
        small_doc).fingerprint()


@pytest.fixture
def served():
    service = PredictionService(coalesce_window_ms=0.0)
    srv = PredictionServer(service).start()
    try:
        yield service, PredictionClient(srv.url)
    finally:
        srv.shutdown()


def test_a_served_rank_builds_no_ops_and_never_falls_back(served):
    service, client = served
    counts = ("trace.decode_slow", "trace.ops_built", "rank.decode")
    before = {n: client.stats()["spans"][n]["count"] for n in counts}
    client.rank(_tracked(31, "sound"), 8)
    after = {n: client.stats()["spans"][n]["count"] for n in counts}
    assert {n: after[n] - before[n] for n in counts} == {
        "trace.decode_slow": 0, "trace.ops_built": 0, "rank.decode": 1}

    rank = service.rank

    def rank_reading_ops(trace, *args, **kwargs):
        assert len(trace.ops) == len(trace.to_arrays().flops)
        return rank(trace, *args, **kwargs)

    service.rank = rank_reading_ops
    client.rank(_tracked(32, "forced"), 8)
    last = {n: client.stats()["spans"][n]["count"] for n in counts}
    assert {n: last[n] - after[n] for n in counts} == {
        "trace.decode_slow": 0, "trace.ops_built": 1, "rank.decode": 1}


def test_assigned_ops_replace_the_documents(small_doc):
    fast = TrackedTrace.from_dict(copy.deepcopy(small_doc))
    ops = _decode_per_op(small_doc).ops
    ops[0].measured_ms *= 3
    fast.ops = ops
    fast.to_arrays(refresh=True)
    again = TrackedTrace(ops=ops, origin_device=fast.origin_device,
                         label=fast.label)
    assert fast.run_time_ms == again.run_time_ms
    assert fast.fingerprint() == again.fingerprint()
