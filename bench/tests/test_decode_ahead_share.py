"""``decode_ahead_share`` on hand-made rings: the share of decode calls
dispatched before their pass's read in the traced ticks, and None where the
program does not count them."""
import sys
import types

import pytest

from bench import run, trace
from repro import obs

T0, ON = 100.0, 1.0     # perf_counter at the window's start; slice start
P0 = round((T0 + ON) * 1e9)     # the ring's ns at ``traced``
LO = 1_000_000                  # the trace's ns at ``traced``


def tick(i, start_ns, counts):
    return obs.Span(i, -1, "tuner.tick", P0 + start_ns, P0 + start_ns
                    + 100_000, {}, counts)


def case(monkeypatch, counters, *ticks):
    r = obs.Recorder()
    r.ring.extend(ticks)
    r.counters.update(counters)
    monkeypatch.setattr(obs, "spans", r.spans)
    monkeypatch.setattr(obs, "snapshot", r.snapshot)
    ctx = types.SimpleNamespace(tracer=types.SimpleNamespace(
        t0=T0, on=ON, off=1.5))
    cell = types.SimpleNamespace(ticks=[
        types.SimpleNamespace(start=ON, end=ON + 100e-6, waves=[]),
        types.SimpleNamespace(start=ON + 1.0, end=ON + 1.0001, waves=[])])
    tr = trace.Trace({"ops": {}, "programs": {},
                      "host": [["traced", LO, 500_000_000]]},
                     LO, LO + 500_000_000)
    return ctx, {"trace": tr, "records": {"cell": cell}}


def read(ctx, res):
    return run.read_metric("decode_ahead_share", ctx, res)


def test_share_of_the_traced_ticks(monkeypatch):
    # the second tick ends after the slice and does not count
    ctx, res = case(
        monkeypatch, {"engine.decode_ahead": 40},
        tick(1, 0, {"engine.decode_steps": 8, "engine.decode_ahead": 6}),
        tick(2, 10**9, {"engine.decode_steps": 9, "engine.decode_ahead": 9}))
    assert read(ctx, res) == pytest.approx(75.0)


def test_no_decode_went_ahead(monkeypatch):
    """A program that counts them, on ticks whose rows could all end on
    EOS."""
    ctx, res = case(monkeypatch, {"engine.decode_ahead": 0},
                    tick(1, 0, {"engine.decode_steps": 4}))
    assert read(ctx, res) == 0.0


@pytest.mark.parametrize("what", ["no counter", "no decode", "no obs"])
def test_nothing_to_read(monkeypatch, what):
    counters = {} if what == "no counter" else {"engine.decode_ahead": 3}
    counts = {} if what == "no decode" else {"engine.decode_steps": 4}
    ctx, res = case(monkeypatch, counters, tick(1, 0, counts))
    if what == "no obs":
        import repro

        monkeypatch.delattr(repro, "obs")
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(ctx, res) is None
