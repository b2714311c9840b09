"""Share of (trace, device) lookups the planner's result cache answered
over the window: ``/stats`` ``cache`` hits / (hits + misses)."""


def read(ctx):
    if "stats_before" not in ctx:
        return None
    a, b = ctx["stats_before"]["cache"], ctx["stats_after"]["cache"]
    hits, misses = b["hits"] - a["hits"], b["misses"] - a["misses"]
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
