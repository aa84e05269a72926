"""Model FLOPs of the traced ticks over their device-busy time and the
chip's bf16 peak.

FLOPs: each wave's prompts (``flops.prefill_flops``) and each of its rows'
tokens after the first (``flops.decode_flops`` at its position), for the
waves the ticks served and the waves their live trials timed; rows that keep
decoding after their answer is complete are waste and do not count.  Device
time: the union of device operations inside the ticks' spans.
"""
from bench import flops, lm
from bench.drivers.serve import traced_ticks


def read(ctx, res):
    tr = res.get("trace")
    ticks = traced_ticks(ctx, res)
    if not ticks:
        return None
    m = lm.dims(ctx.config)
    work = busy = 0.0
    for t, lo, hi in ticks:
        busy += tr.busy_ns(lo, hi) * 1e-9
        for w in t.waves:
            for a in w.answers:
                work += flops.prefill_flops(m, w.prompt_len) + sum(
                    flops.decode_flops(m, w.prompt_len + k)
                    for k in range(a - 1))
    if busy <= 0:
        return None
    return 100.0 * work / busy / ctx.peaks["bf16_flops_per_s"]
