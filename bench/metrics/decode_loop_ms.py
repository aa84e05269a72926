"""Mean host time of one pass of the engine's decode loop (``engine.step``
spans) in the ticks that ran whole inside the traced slice."""
from bench.metrics import _spans


def read(ctx, res):
    return _spans.mean_ms(_spans.named(ctx, res, "engine.step"))
