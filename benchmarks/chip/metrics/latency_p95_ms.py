"""The 95th percentile of one endpoint's latency over every request of
the traced window, as the load generator timed it from each request's due
time (``latency_p95_ms.<endpoint>``): the served tail, read per layer
where its runs spread too widely to hold a bound end to end."""

from benchmarks.chip import bench


def read(ctx):
    if "requests" not in ctx or "." not in ctx.get("metric", ""):
        return None
    path = "/" + ctx["metric"].split(".", 1)[1]
    lat = [o.latency_s * 1e3 if o is not None and o.status == 200
           else float("inf")
           for r, o in zip(ctx["requests"], ctx["outcomes"])
           if r.path == path]
    return bench.nearest_rank(lat, 0.95) if lat else None
