"""Requests per coalesced engine batch over the window (service layer):
the ``/stats`` requests the coalescer accepted over its
``coalescing.batches``."""


def read(ctx):
    if "stats_before" not in ctx:
        return None
    a, b = ctx["stats_before"], ctx["stats_after"]
    batches = b["coalescing"]["batches"] - a["coalescing"]["batches"]
    if batches <= 0:
        return None
    accepted = sum(b["requests"][k] - a["requests"].get(k, 0)
                   for k in b["requests"])
    return accepted / batches
