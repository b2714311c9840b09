"""The threaded front door's time per POST over the window: reading the
body (``http.read``) and encoding and writing the answer
(``http.reply``), per body read.  Every POST reads a body, answered from
the response cache or not, where ``/stats`` ``requests`` counts only
those that reach the coalescer."""

from benchmarks.chip.metrics import _spans


def read(ctx):
    d = _spans.delta(ctx)
    if d is None or d["http.read"]["count"] <= 0:
        return None
    return 1e3 * ((d["http.read"]["seconds"] + d["http.reply"]["seconds"])
                  / d["http.read"]["count"])
