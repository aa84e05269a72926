"""``moe_inplace_share`` on hand-made rings: the share of the traced decode
calls' MoE layers that read their held experts in place, and None where the
program does not count them."""
import sys
import types

import pytest

from bench import lm, run, trace
from repro import obs

T0, ON = 100.0, 1.0     # perf_counter at the window's start; slice start
P0 = round((T0 + ON) * 1e9)     # the ring's ns at ``traced``
LO = 1_000_000                  # the trace's ns at ``traced``
MOE_LAYERS = 26                 # DeepSeek-V2-Lite: 27 layers, 1 dense


def tick(i, start_ns, counts):
    return obs.Span(i, -1, "tuner.tick", P0 + start_ns, P0 + start_ns
                    + 100_000, {}, counts)


def case(monkeypatch, counters, *ticks):
    r = obs.Recorder()
    r.ring.extend(ticks)
    r.counters.update(counters)
    monkeypatch.setattr(obs, "spans", r.spans)
    monkeypatch.setattr(obs, "snapshot", r.snapshot)
    ctx = types.SimpleNamespace(
        tracer=types.SimpleNamespace(t0=T0, on=ON, off=1.5),
        config=lm.load_config("deepseek-v2-lite"))
    cell = types.SimpleNamespace(ticks=[
        types.SimpleNamespace(start=ON, end=ON + 100e-6, waves=[]),
        types.SimpleNamespace(start=ON + 1.0, end=ON + 1.0001, waves=[])])
    tr = trace.Trace({"ops": {}, "programs": {},
                      "host": [["traced", LO, 500_000_000]]},
                     LO, LO + 500_000_000)
    return ctx, {"trace": tr, "records": {"cell": cell}}


def read(ctx, res):
    return run.read_metric("moe_inplace_share", ctx, res)


def test_every_layer_of_every_call_in_place(monkeypatch):
    # the second tick ends after the slice and does not count
    ctx, res = case(
        monkeypatch, {"moe.inplace_layers": 1},
        tick(1, 0, {"engine.decode_steps": 8,
                    "moe.inplace_layers": 8 * MOE_LAYERS}),
        tick(2, 10**9, {"engine.decode_steps": 9}))
    assert read(ctx, res) == pytest.approx(100.0)


def test_share_of_the_layers(monkeypatch):
    ctx, res = case(monkeypatch, {"moe.inplace_layers": 1},
                    tick(1, 0, {"engine.decode_steps": 4,
                                "moe.inplace_layers": MOE_LAYERS}))
    assert read(ctx, res) == pytest.approx(25.0)


def test_no_layer_in_place(monkeypatch):
    """A program that counts them, on calls too large to read in place."""
    ctx, res = case(monkeypatch, {"moe.inplace_layers": 0},
                    tick(1, 0, {"engine.decode_steps": 4}))
    assert read(ctx, res) == 0.0


@pytest.mark.parametrize("what", ["no counter", "no decode", "no obs"])
def test_nothing_to_read(monkeypatch, what):
    counters = {} if what == "no counter" else {"moe.inplace_layers": 3}
    counts = {} if what == "no decode" else {"engine.decode_steps": 4,
                                             "moe.inplace_layers": 3}
    ctx, res = case(monkeypatch, counters, tick(1, 0, counts))
    if what == "no obs":
        import repro

        monkeypatch.delattr(repro, "obs")
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(ctx, res) is None
