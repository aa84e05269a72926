"""A traced closed-loop run reaches the retune its traffic asks for.

On the chip the profiler's stop holds the host for tens of seconds.  The tick
after it serves the mix due when it begins, not the one due when the stop
began: here the stop holds the host past the docs phase's start (0.3 of the
window), and the next tick serves docs and retunes live."""
import time

import tiny
from bench import run

SECONDS = 8.0
DOCS_FROM = 0.3 * SECONDS


def test_tick_after_the_profilers_stop_serves_the_mix_then_due(monkeypatch):
    stop = run.Tracer.stop

    def slow_stop(self):
        stop(self)
        time.sleep(max(0.0, self.t0 + DOCS_FROM + 0.2 - time.perf_counter()))

    monkeypatch.setattr(run.Tracer, "stop", slow_stop)
    ctx = tiny.context("closed", seed=7, seconds=SECONDS, trace=True)
    ctx.tracer.length = 0.01        # the slice holds the first tick
    res = run.drive(ctx)
    out = run.result(ctx, res)
    assert out["correct"], out["checks"]
    off = ctx.tracer.off
    assert off is not None and off < DOCS_FROM     # stopped during chat
    after = [t for t in res["records"]["cell"].ticks if t.start >= off]
    assert after[0].mix == "docs" and after[0].start >= DOCS_FROM
    assert after[0].live_trials > 0
    assert res["records"]["retunes"]
    assert out["metrics"]["live_trials_per_retune"]["value"] > 0
    assert out["metrics"]["trial_wave_s"]["value"] > 0
