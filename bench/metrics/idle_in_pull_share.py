"""Share of the device's idle time in the traced ticks during which the
host was reading tokens (innermost program span ``engine.pull``)."""
from bench.metrics import _spans


def read(ctx, res):
    return _spans.idle_share(ctx, res, "engine.pull")
