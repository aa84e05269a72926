"""Routed rows per held expert that a decode call touched: the counters
``moe.pairs_held`` over ``moe.experts_touched``, moved inside the ticks that
ran whole inside the traced slice (each call counts both over its MoE
layers).  It shows how many rows share each read of an expert's weights.
None on a program whose decode does not count them."""
from bench.metrics import _spans


def read(ctx, res):
    obs = _spans.program_obs()
    if obs is None or "moe.experts_touched" not in obs.snapshot():
        return None
    touched = _spans.counted(ctx, res, "moe.experts_touched")
    if not touched:
        return None
    return _spans.counted(ctx, res, "moe.pairs_held") / touched
