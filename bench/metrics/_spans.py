"""The program's spans and counters (``repro.obs``) in the ticks that ran
whole inside the traced slice, for the readers of program spans.

Ring records keep ``perf_counter_ns`` times; the ``traced`` annotation, read
on both clocks when the profiler started, maps them onto the trace's
(``obs.trace_ns``).  A program without ``repro.obs`` records nothing, and
each reader then returns None.
"""
import collections

from bench import trace
from bench.drivers.serve import traced_ticks


def program_obs():
    """``repro.obs``, or None where the program has none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def traced(ctx, res):
    """(start ns, end ns on the trace's clock, the ring's records inside)
    of every tick that ran whole inside the traced slice."""
    obs = program_obs()
    if obs is None:
        return []
    t0 = ctx.tracer.t0
    return [(lo, hi, obs.spans(t0 + t.start, t0 + t.end))
            for t, lo, hi in traced_ticks(ctx, res)]


def named(ctx, res, name):
    return [r for _, _, recs in traced(ctx, res) for r in recs
            if r.name == name]


def mean_ms(records):
    if not records:
        return None
    return sum(r.ns for r in records) / len(records) * 1e-6


def counted(ctx, res, name):
    """The counter's moves over the traced ticks' ``tuner.tick`` spans."""
    return sum(r.counts.get(name, 0) for r in named(ctx, res, "tuner.tick"))


def idle_share(ctx, res, name):
    """Share of the busiest device's idle ns in the traced ticks during
    which the host's innermost program span was ``name``: its spans less
    their children's."""
    obs, tr = program_obs(), res.get("trace")
    ticks = traced(ctx, res)
    if tr is None or not tr.devices or not ticks:
        return None
    anchor = (ctx.tracer.t0 + ctx.tracer.on, tr.lo)

    def on_trace(r):
        return obs.trace_ns(r.start, anchor), obs.trace_ns(r.end, anchor)

    dev = max(tr.devices, key=lambda k: trace.measure(
        tr.busy_union[k], tr.lo, tr.hi))
    idle = inside = 0.0
    found = 0
    for lo, hi, recs in ticks:
        kids = collections.defaultdict(list)
        for r in recs:
            kids[r.parent].append(on_trace(r))
        own = []
        for r in recs:
            if r.name == name:
                found += 1
                own += trace.subtract([on_trace(r)], trace.union(kids[r.id]))
        gaps = trace.subtract([(lo, hi)], tr.busy_union[dev])
        g = trace.measure(gaps, lo, hi)
        idle += g
        inside += g - trace.measure(trace.subtract(gaps, trace.union(own)),
                                    lo, hi)
    if not found or idle <= 0:
        return None
    return 100.0 * inside / idle
