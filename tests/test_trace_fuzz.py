"""Property fuzz of the trace wire decoder (hypothesis-gated).

``TrackedTrace.from_json`` must be TOTAL over arbitrary documents:
every input either decodes to a trace whose re-serialization preserves
its fingerprint, or raises exactly
:class:`~repro.core.trace.TraceValidationError` (the front ends' 400
path) — never a KeyError/TypeError/numpy crash from deep inside the
decoder.  Deterministic poison cases live in ``test_durability.py``;
this module explores the input space when hypothesis is installed (a
dev-only dependency — the module skips cleanly without it).
"""

import json

import jax.numpy as jnp
import pytest

from repro.core import OperationTracker
from repro.core.trace import TraceValidationError, TrackedTrace

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, strategies as st  # noqa: E402

_json_values = st.recursive(
    st.none() | st.booleans() | st.integers(min_value=-10**6, max_value=10**6)
    | st.floats(allow_nan=False) | st.text(max_size=12),
    lambda children: (st.lists(children, max_size=3)
                      | st.dictionaries(st.text(max_size=8), children,
                                        max_size=3)),
    max_leaves=10)


@given(doc=_json_values)
def test_fuzz_from_json_decodes_or_rejects_cleanly(doc):
    """Arbitrary JSON either decodes to a trace that round-trips with a
    stable fingerprint, or raises exactly TraceValidationError — never
    a KeyError/TypeError from deep inside the decoder."""
    try:
        trace = TrackedTrace.from_json(json.dumps(doc))
    except TraceValidationError:
        return
    back = TrackedTrace.from_json(trace.to_json())
    assert back.fingerprint() == trace.fingerprint()


@pytest.fixture(scope="module")
def valid_doc():
    """A valid trace document, built once — outside the examples that
    hypothesis times."""
    return json.dumps(OperationTracker("T4").track(
        lambda w, x: jnp.sum(jnp.tanh(x @ w)), jnp.zeros((12, 24)),
        jnp.zeros((8, 12)), label="fuzz").to_dict())


@given(field=st.sampled_from(["origin_device", "label", "ops"]),
       value=_json_values)
def test_fuzz_mutated_trace_documents(valid_doc, field, value):
    """Mutating one top-level field of a VALID document keeps the same
    contract — the decoder validates fields, not just overall shape."""
    doc = json.loads(valid_doc)
    doc[field] = value
    try:
        trace = TrackedTrace.from_dict(doc)
    except TraceValidationError:
        return
    back = TrackedTrace.from_json(trace.to_json())
    assert back.fingerprint() == trace.fingerprint()
