"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window."""


def read(ctx):
    prof = ctx.get("profile")
    if prof is None or ctx["window_s"] <= 0 or prof["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - prof["busy_s"] / ctx["window_s"])
