"""Wire decode per request over the window: ``json.loads`` of the body
and the trace document's decode (``rank.decode``)."""

from benchmarks.chip.metrics import _spans


def read(ctx):
    return _spans.per_call_ms(ctx, "rank.decode")
