"""Mean host time of a wave's prefill, from padding its prompts to the
dispatch of its first token (``engine.prefill`` spans), in the ticks that
ran whole inside the traced slice."""
from bench.metrics import _spans


def read(ctx, res):
    return _spans.mean_ms(_spans.named(ctx, res, "engine.prefill"))
