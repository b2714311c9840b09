"""Share of the traced window the server's collector held the
interpreter: every generation's pauses (``/stats`` ``spans.gc``) over
the window."""

from benchmarks.chip.metrics import _spans


def read(ctx):
    d = _spans.delta(ctx)
    if d is None or ctx.get("window_s", 0) <= 0:
        return None
    return 100.0 * sum(g["seconds"] for g in d["gc"].values()) \
        / ctx["window_s"]
