"""The fused MLP scorer kernel's share of its roofline over the window.

Kernel time is the device time of the scorer's operations in the
profile.  The least time is the larger of the window's useful MLP
operations over peak FLOP/s and their bytes over peak bandwidth
(``work.mlp_work``), for the cells the result cache missed."""

from benchmarks.chip import work


def read(ctx):
    if "profile" not in ctx or "stats_before" not in ctx:
        return None
    return work.shares(ctx)[0]
