"""Share of decode calls dispatched before their pass's token read: the
counters ``engine.decode_ahead`` over ``engine.decode_steps``, moved inside
the ticks that ran whole inside the traced slice, in %.  None on a program
whose engine does not count them."""
from bench.metrics import _spans


def read(ctx, res):
    obs = _spans.program_obs()
    if obs is None or "engine.decode_ahead" not in obs.snapshot():
        return None
    steps = _spans.counted(ctx, res, "engine.decode_steps")
    if not steps:
        return None
    return 100.0 * _spans.counted(ctx, res, "engine.decode_ahead") / steps
