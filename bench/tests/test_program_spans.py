"""The readers of the program's spans and counters, on hand-made rings and
traces: the ring's clock maps onto the trace's through the ``traced``
anchor, only ticks that ran whole inside the traced slice count, idle time
goes to the host's innermost span (a gap split across two spans included),
and each reader returns None where there is nothing to read."""
import sys
import types

import pytest

from bench import run, trace
from repro import obs

T0 = 100.0              # perf_counter at the window's start, s
ON, OFF = 1.0, 1.5      # the traced slice, s from the window's start
LO = 1_000_000          # the trace's ns at ``traced``
P0 = round((T0 + ON) * 1e9)     # the ring's ns at ``traced``
DEV = "/device:TPU:0"

SATURATE = ("decode_loop_ms", "token_pull_ms", "token_pulls_per_step",
            "prefill_host_ms", "idle_in_pull_share", "idle_in_prefill_share")


def rec(i, parent, name, a, b, counts=None, base=P0):
    return obs.Span(i, parent, name, base + a, base + b, {}, counts)


# one traced tick of 100 µs from ``traced`` on: a wave of a prefill and three
# loop passes, two of them followed by a decode call
TICK = [
    rec(1, -1, "tuner.tick", 0, 100_000,
        {"engine.decode_steps": 2, "engine.host_pulls": 6}),
    rec(2, 1, "engine.wave", 1_000, 99_000, {}),
    rec(3, 2, "engine.prefill", 1_000, 21_000),
    rec(4, 2, "engine.step", 21_000, 51_000),
    rec(5, 4, "engine.pull", 21_000, 41_000),
    rec(6, 2, "engine.step", 51_000, 91_000),
    rec(7, 6, "engine.pull", 51_000, 61_000),
    rec(8, 2, "engine.step", 91_000, 99_000),
    rec(9, 8, "engine.pull", 91_000, 99_000),
]
# device ops (ns after ``traced``): idle 0-15, 25-41, 50-61 and 88-100 µs
BUSY = [(15_000, 10_000), (41_000, 9_000), (61_000, 27_000)]
# a tick after the slice, whose spans must not count
LATE = [
    rec(11, -1, "tuner.tick", 0, 2_000_000,
        {"engine.decode_steps": 9, "engine.host_pulls": 90}, base=P0 + 10**9),
    rec(12, 11, "engine.step", 0, 1_000_000, base=P0 + 10**9),
    rec(13, 12, "engine.pull", 0, 900_000, base=P0 + 10**9),
]


def retune_spans(i, start_s, trial_s, rank_s, other_s):
    """A retune tick: ranking (no span of its own), two trials, and
    ``other_s`` besides."""
    base = round((T0 + start_s) * 1e9)
    t = round(trial_s * 1e9)
    r = round(rank_s * 1e9)
    total = r + 2 * t + round(other_s * 1e9)
    return [rec(i, -1, "tuner.tick", 0, total + 10, base=base),
            rec(i + 1, i, "tuner.retune", 5, total + 5, base=base),
            rec(i + 3, i + 1, "tuner.trial", 5 + r, 5 + r + t, base=base),
            rec(i + 4, i + 1, "tuner.trial", 5 + r + t, 5 + r + 2 * t,
                base=base)]


RETUNES = retune_spans(20, 3.0, 0.3, 0.1, 0.3) + \
    retune_spans(30, 5.0, 0.5, 0.05, 0.15)


def tick(start_s, end_s):
    return types.SimpleNamespace(start=start_s, end=end_s, waves=[])


@pytest.fixture
def ring(monkeypatch):
    r = obs.Recorder()
    r.ring.extend(TICK + LATE + RETUNES)
    monkeypatch.setattr(obs, "spans", r.spans)
    return r


def case(devices=True, on=ON, traced=True):
    ops = {DEV: [["fusion", LO + s, d] for s, d in BUSY]} if devices else {}
    tr = trace.Trace({"ops": ops, "programs": {},
                      "host": [["traced", LO, 500_000_000]]},
                     LO, LO + 500_000_000) if traced else None
    ctx = types.SimpleNamespace(tracer=types.SimpleNamespace(
        t0=T0, on=on, off=OFF))
    ticks = [tick(ON, ON + 100e-6), tick(ON + 1.0, ON + 1.002),
             tick(3.0, 4.5), tick(5.0, 6.5)]
    res = {"trace": tr, "records": {
        "cell": types.SimpleNamespace(ticks=ticks),
        "retunes": ticks[2:]}}
    return ctx, res


def read(name, ctx, res):
    return run.read_metric(name, ctx, res)


def test_span_readers_read_the_traced_tick(ring):
    ctx, res = case()
    assert read("decode_loop_ms", ctx, res) == pytest.approx(
        (30 + 40 + 8) / 3 * 1e-3)
    assert read("token_pull_ms", ctx, res) == pytest.approx(
        (20 + 10 + 8) / 3 * 1e-3)
    assert read("prefill_host_ms", ctx, res) == pytest.approx(20e-3)
    assert read("token_pulls_per_step", ctx, res) == pytest.approx(3.0)


def test_idle_goes_to_the_innermost_span(ring):
    """Idle 54 µs: 0-1 the tick's own, 1-15 prefill, 25-41 pull, 50-51 a
    step's own and 51-61 the next pull (one gap, two spans), 88-91 a step's
    own, 91-99 pull, 99-100 the tick's own."""
    ctx, res = case()
    assert read("idle_in_pull_share", ctx, res) == pytest.approx(
        100 * 34 / 54)
    assert read("idle_in_prefill_share", ctx, res) == pytest.approx(
        100 * 14 / 54)
    from bench.metrics import _spans
    shares = {n: _spans.idle_share(ctx, res, n) for n in (
        "tuner.tick", "engine.wave", "engine.step", "engine.pull",
        "engine.prefill")}
    assert shares["engine.wave"] == pytest.approx(0.0)
    assert shares["tuner.tick"] == pytest.approx(100 * 2 / 54)
    assert shares["engine.step"] == pytest.approx(100 * 4 / 54)
    assert sum(shares.values()) == pytest.approx(100.0)


def test_retune_self_time_leaves_out_trials_only(ring):
    ctx, res = case(traced=False)
    # (0.1 + 0.3) and (0.05 + 0.15): ranking counts, the trials do not
    assert read("retune_self_s", ctx, res) == pytest.approx(0.3, abs=1e-6)


def test_retune_self_time_reads_retunes_outside_the_window(ring):
    """A window whose retune never began (the profiler's stop held the
    host past its end) still reads the set-up's retunes from the ring."""
    ctx, res = case()
    res["records"]["retunes"] = []
    assert read("retune_self_s", ctx, res) == pytest.approx(0.3, abs=1e-6)


@pytest.mark.parametrize("what", ["no trace", "no tick in the slice",
                                  "no device"])
def test_saturate_readers_without_a_reading(ring, what):
    ctx, res = case(traced=what != "no trace",
                    on=1.2 if what == "no tick in the slice" else ON,
                    devices=what != "no device")
    got = {n: read(n, ctx, res) for n in SATURATE}
    if what == "no device":
        assert got["idle_in_pull_share"] is None
        assert got["idle_in_prefill_share"] is None
        assert got["decode_loop_ms"] is not None
    else:
        assert got == dict.fromkeys(SATURATE)


def test_readers_of_a_program_without_spans(monkeypatch):
    """The parent of the span readers has no ``repro.obs``."""
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    ctx, res = case()
    for name in SATURATE + ("retune_self_s",):
        assert read(name, ctx, res) is None


def test_empty_ring_and_no_retunes(monkeypatch):
    monkeypatch.setattr(obs, "spans", obs.Recorder().spans)
    ctx, res = case()
    for name in SATURATE + ("retune_self_s",):
        assert read(name, ctx, res) is None
    res["records"]["retunes"] = []
    assert read("retune_self_s", ctx, res) is None


def test_no_retune_in_the_ring(monkeypatch):
    """Ticks that tuned nothing live: no ``tuner.retune`` span."""
    r = obs.Recorder()
    r.ring.extend(TICK + LATE)
    monkeypatch.setattr(obs, "spans", r.spans)
    ctx, res = case()
    assert read("retune_self_s", ctx, res) is None
