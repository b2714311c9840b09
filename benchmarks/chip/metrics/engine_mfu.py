"""The engine pass's useful MLP FLOP/s over the window, as a share of the
chip's peak: it still bounds the scoring work once a later change folds
the scorer into a larger device pass."""

from benchmarks.chip import work


def read(ctx):
    if "profile" not in ctx or "stats_before" not in ctx:
        return None
    return work.shares(ctx)[1]
