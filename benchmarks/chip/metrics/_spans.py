"""What the readers of the program's span totals share: the ``/stats``
``spans`` block's change over the traced window."""


def delta(ctx):
    """``stats_after["spans"]`` minus ``stats_before["spans"]``, key by
    key; None where the program serves no ``spans`` block."""
    if "stats_before" not in ctx:
        return None
    a = ctx["stats_before"].get("spans")
    b = ctx["stats_after"].get("spans")
    if a is None or b is None:
        return None
    return _minus(b, a)


def _minus(b, a):
    return {k: _minus(v, a[k]) if isinstance(v, dict) else v - a[k]
            for k, v in b.items()}


def per_call_ms(ctx, name, field="seconds"):
    """Milliseconds of ``name``'s ``field`` per call over the window, or
    None where nothing was recorded."""
    d = delta(ctx)
    if d is None or d[name]["count"] <= 0:
        return None
    return 1e3 * d[name][field] / d[name]["count"]
