"""A query's wait in the coalescer over the window: from joining the
queue to a leader taking its batch (``rank.queue``)."""

from benchmarks.chip.metrics import _spans


def read(ctx):
    return _spans.per_call_ms(ctx, "rank.queue")
