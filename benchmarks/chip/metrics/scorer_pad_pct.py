"""Share of the scorer's launched rows that were padding over the window:
``/stats`` ``scorer_rows`` padded / (real + padded), the zero rows that
fill whole row blocks and the jit bucket.  None on a program that counts
no scorer rows, or where the scorer ran no rows."""


def read(ctx):
    if "stats_before" not in ctx:
        return None
    a = ctx["stats_before"].get("scorer_rows")
    b = ctx["stats_after"].get("scorer_rows")
    if a is None or b is None:
        return None
    real, padded = b["real"] - a["real"], b["padded"] - a["padded"]
    if real + padded <= 0:
        return None
    return 100.0 * padded / (real + padded)
