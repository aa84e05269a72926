"""Share of the device's idle time in the traced ticks during which the
host was in a wave's prefill (innermost program span ``engine.prefill``)."""
from bench.metrics import _spans


def read(ctx, res):
    return _spans.idle_share(ctx, res, "engine.prefill")
