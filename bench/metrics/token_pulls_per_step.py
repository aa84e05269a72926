"""Device-to-host token reads per decode call: the counters
``engine.host_pulls`` over ``engine.decode_steps``, moved inside the ticks
that ran whole inside the traced slice."""
from bench.metrics import _spans


def read(ctx, res):
    steps = _spans.counted(ctx, res, "engine.decode_steps")
    if not steps:
        return None
    return _spans.counted(ctx, res, "engine.host_pulls") / steps
