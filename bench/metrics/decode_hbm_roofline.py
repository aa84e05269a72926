"""Least HBM time of the decode calls over their device time.

Bytes per call: every weight once, plus the cache of the positions in use
read and one position written per row (``flops.decode_bytes``).  The calls
are those of the ticks that ran whole in the traced slice, their live
trials' waves included; the reading is left out when the trace does not hold
exactly the calls those waves make.
"""
from bench import flops, lm
from bench.drivers.serve import traced_ticks

PROGRAM = "jit_decode"


def read(ctx, res):
    tr = res.get("trace")
    ticks = traced_ticks(ctx, res)
    if not ticks:
        return None
    m = lm.dims(ctx.config)
    params = lm.param_count(ctx.config)
    calls = tr.program_calls(PROGRAM)
    need = secs = 0.0
    for t, lo, hi in ticks:
        inside = [d for s, d in calls if lo <= s <= hi]
        if len(inside) != sum(w.decode_calls for w in t.waves):
            return None
        secs += sum(inside) * 1e-9
        for w in t.waves:
            for k in range(w.decode_calls):
                need += flops.decode_bytes(
                    m, params, [w.prompt_len + k] * len(w.answers))
    if secs <= 0:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
