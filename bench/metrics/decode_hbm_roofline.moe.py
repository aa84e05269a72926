"""Least HBM time of the decode calls of a model with experts over their
device time.

Bytes per call (the configuration's family, ``decode_bytes``): every weight
but the routed experts once, of the embedding only the rows' own rows, the
cache of the positions in use read and one position written per row; then,
over all the calls, ``expert_bytes`` for each held expert a call touched
(the counter ``moe.experts_touched`` of the same ticks).  The calls are
those of the ticks that ran whole in the traced slice, their live trials'
waves included; the reading is left out when the trace does not hold
exactly the calls those waves make, and where the family or the program
does not count experts.
"""
from bench import lm
from bench.drivers.serve import traced_ticks
from bench.metrics import _spans

PROGRAM = "jit_decode"


def read(ctx, res):
    fam = lm.family(ctx.config)
    obs = _spans.program_obs()
    tr = res.get("trace")
    ticks = traced_ticks(ctx, res)
    if (not hasattr(fam, "expert_bytes") or obs is None
            or "moe.experts_touched" not in obs.snapshot() or not ticks):
        return None
    m = lm.dims(ctx.config)
    calls = tr.program_calls(PROGRAM)
    need = secs = 0.0
    for t, lo, hi in ticks:
        inside = [d for s, d in calls if lo <= s <= hi]
        if len(inside) != sum(w.decode_calls for w in t.waves):
            return None
        secs += sum(inside) * 1e-9
        for w in t.waves:
            for k in range(w.decode_calls):
                need += fam.decode_bytes(
                    m, [w.prompt_len + k] * len(w.answers))
    need += (_spans.counted(ctx, res, "moe.experts_touched")
             * fam.expert_bytes(m))
    if secs <= 0:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
