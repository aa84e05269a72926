"""Seconds of a live retune outside its timed trials: each ``tuner.retune``
span's duration less its ``tuner.trial`` children (ranking, the portable
model, trial engines' builds and warm-ups, the store), per retune the ring
holds.

That is every live retune of the run, the set-up's first tune included: a
retune in the window may start after the window's end when the profiler's
stop holds the host, and a set-up tune on a cold store is the same work
(a model trained for its bucket, its trials, a store write)."""
from bench.metrics import _spans


def read(ctx, res):
    obs = _spans.program_obs()
    if obs is None:
        return None
    recs = obs.spans()
    own = [obs.self_ns(r, recs, ("tuner.trial",)) for r in recs
           if r.name == "tuner.retune"]
    if not own:
        return None
    return sum(own) / len(own) * 1e-9
