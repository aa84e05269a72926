"""Host time per decode-loop pass spent reading tokens from the device
(``engine.pull`` spans over ``engine.step`` spans), in the ticks that ran
whole inside the traced slice."""
from bench.metrics import _spans


def read(ctx, res):
    steps = _spans.named(ctx, res, "engine.step")
    if not steps:
        return None
    pulls = _spans.named(ctx, res, "engine.pull")
    return sum(r.ns for r in pulls) / len(steps) * 1e-6
