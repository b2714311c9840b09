"""One scorer call as the host sees it over the window (``engine.score``):
normalizing and padding the rows, the transfer to the device, the
launch and the readback, against the kernel's device time that
``scorer_roofline`` reads."""

from benchmarks.chip.metrics import _spans


def read(ctx):
    return _spans.per_call_ms(ctx, "engine.score")
