"""The engine pass's own host time per pass over the window: the
``engine.pass`` span less the scorer calls and collector pauses inside
it (its self time), i.e. wave scaling, features and stacking."""

from benchmarks.chip.metrics import _spans


def read(ctx):
    return _spans.per_call_ms(ctx, "engine.pass", "self_seconds")
