"""Serving cells: requests through the program's online tuner, tick by tick.

Set-up makes the weights from the seed, builds ``EngineBackend`` and
``OnlineAutotuner`` as the workload file sets them, serves one tick of the
set-up mix (the tuner's first live tune), and warms every engine shape the
window can reach: the tuned configuration at every wave size the loop can
make, and every configuration a later mix's retune may try.  The window then
offers the traffic in ticks: each ``OnlineAutotuner.serve`` call takes the
requests that are due (open loop) or one request from every client (closed
loop), and all of them are answered when it returns.

After the window a sample of the answered requests, drawn from the seed, is
checked against the plain reference (see ``check``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Dict, List

import numpy as np

from bench import check, lm, traffic


@dataclasses.dataclass
class Served:
    uid: int
    mix: str
    prompt: np.ndarray
    answer_len: int
    due: float                  # s from the window's start
    done: float = math.nan      # s from the window's start; nan: unanswered
    tokens: List[int] = dataclasses.field(default_factory=list)
    tick: int = -1
    config_key: tuple = ()


@dataclasses.dataclass
class Wave:
    """One engine wave: its rows' answer lengths and its padded prompt."""
    answers: List[int]
    prompt_len: int

    @property
    def decode_calls(self) -> int:
        # the first token comes from the prefill, each later one from a call
        return max(self.answers) - 1


def waves_of(reqs: List[Served], batch: int) -> List[Wave]:
    """The waves ``ServeEngine.generate`` makes of ``reqs``."""
    return [Wave([r.answer_len for r in reqs[i:i + batch]],
                 max(len(r.prompt) for r in reqs[i:i + batch]))
            for i in range(0, len(reqs), batch)]


@dataclasses.dataclass
class Tick:
    mix: str
    start: float
    end: float
    n: int
    config: Dict[str, int]
    drift: bool
    reused: bool
    live_trials: int
    history: list
    waves: List[Wave]           # the live trials' waves, then the served ones


class ServeCell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.wl = ctx.workload
        self.cfg = ctx.config
        self.m = lm.dims(self.cfg)
        self.uid = 0
        self.served: List[Served] = []
        self.ticks: List[Tick] = []

    # -- set-up -----------------------------------------------------------------
    def build(self):
        import types

        import jax

        from repro.core.hwspec import spec_for_device
        from repro.models.registry import build_model
        from repro.serve.autotune import (EngineBackend, OnlineAutotuner,
                                          ShapeBucketer, serve_space,
                                          stats_from_model)
        from repro.tuning.store import ConfigStore

        arch = lm.arch(self.ctx.config_name, self.cfg)
        model = build_model(arch)
        params = lm.make_weights(self.cfg, self.ctx.seed, "program",
                                 arch.padded_vocab)
        want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                    jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("seeded weights do not match the program's "
                             "parameter tree")
        # EngineBackend initialises its own weights: hand it these instead
        model.init = lambda rng: params
        self.params_count = model.param_count()
        tw = self.wl["tuner"]
        self.backend = EngineBackend(model)
        self.tuner = OnlineAutotuner(
            self.backend, store=ConfigStore(),
            bucketer=ShapeBucketer(**self.wl["bucketer"]),
            space=serve_space(**self.wl["space"]),
            stats=stats_from_model(model),
            hw=spec_for_device(types.SimpleNamespace(device_kind=self.ctx.kind)),
            max_live_trials=tw["max_live_trials"], window=tw["window"],
            calib_n=tw["calib_n"], seed=tw["seed"])
        self.traffic = traffic.Traffic(self.wl, self.m["vocab"],
                                       self.ctx.seconds, self.ctx.seed)

    def requests(self, mix: str, n: int, due: float = 0.0,
                 answer: int = 0, stream: str = "window") -> List[Served]:
        """``n`` requests of ``mix``; ``answer`` > 0 overrides the drawn
        answer length (warm-up waves)."""
        out = []
        for _ in range(n):
            prompt, ans = self.traffic.draw(mix, stream)
            out.append(Served(self.uid, mix, prompt, answer or ans, due))
            self.uid += 1
        return out

    def program_requests(self, reqs: List[Served]):
        from repro.serve.engine import Request

        return [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.answer_len) for r in reqs]

    def wave_sizes(self, batch: int) -> List[int]:
        """Every wave size the window's loop can hand an engine of ``batch``
        slots: any, for an open loop; the clients' count split into waves,
        for a closed one."""
        loop = self.wl["loop"]
        if loop["kind"] == "open":
            return list(range(1, batch + 1))
        c = int(loop["clients"])
        return sorted({min(batch, c), c % batch} - {0})

    def warm(self, mix: str, every_wave: bool):
        """Compile, untimed, every configuration a tune of ``mix`` may time:
        its calibration waves at the mix's prompt length, and with
        ``every_wave`` every wave size the window's loop can hand it."""
        tw = self.wl["tuner"]
        bucketer = self.tuner.bucketer
        spec = self.wl["mixes"][mix]
        plen, new = int(spec["prompt_len"]), int(spec["answer"]["max"])
        edge = sum(bucketer.rep_shape(bucketer.bucket_of(plen, new)))
        need = max(edge, plen + new)
        for cfg in self.tuner.space:
            if int(cfg["MAX_SEQ"]) < need:
                continue
            b = int(cfg["BATCH"])
            self.backend.measure(cfg, self.program_requests(self.requests(
                mix, tw["calib_n"], answer=2, stream="setup")))
            if every_wave:
                for n in self.wave_sizes(b):
                    self.backend.serve(cfg, self.program_requests(
                        self.requests(mix, n, answer=2, stream="setup")))

    def setup(self):
        tw = self.wl["tuner"]
        setup_mix = self.wl["setup_mix"]
        # a cold first run then times its set-up trials as a warm one does
        self.warm(setup_mix, every_wave=False)
        reqs = self.requests(setup_mix, tw["calib_n"], stream="setup")
        self.tuner.serve(self.program_requests(reqs))
        tuned = dict(self.tuner.reports[-1].config)
        for n in self.wave_sizes(tuned["BATCH"]):
            self.backend.serve(tuned, self.program_requests(
                self.requests(setup_mix, n, answer=2, stream="setup")))
        for mix in self.traffic.window_mixes():
            if mix != setup_mix:
                self.warm(mix, every_wave=True)

    # -- the window -------------------------------------------------------------
    def tick(self, reqs: List[Served], t0: float, mix: str):
        import jax

        start = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation(f"tick:{mix}"):
            outputs, rep = self.tuner.serve(self.program_requests(reqs))
        end = time.perf_counter() - t0
        waves = []
        if rep.live_trials:
            # OnlineAutotuner.serve times its first calib_n requests of the
            # dominant bucket under each configuration it tries
            bucketer = self.tuner.bucketer
            calib = [r for r in reqs if bucketer.bucket_of(
                len(r.prompt), r.answer_len).key == rep.bucket]
            calib = (calib or reqs)[:self.tuner.calib_n]
            for i, _ in rep.history:
                waves += waves_of(calib, int(self.tuner.space[i]["BATCH"]))
        waves += waves_of(reqs, int(rep.config["BATCH"]))
        for r in reqs:
            r.done, r.tick = end, len(self.ticks)
            r.tokens = [int(t) for t in outputs.get(r.uid, [])]
            r.config_key = tuple(sorted(rep.config.items()))
        self.ticks.append(Tick(mix, start, end, len(reqs), dict(rep.config),
                               rep.drift, rep.reused, rep.live_trials,
                               [list(h) for h in rep.history], waves))
        self.served.extend(reqs)

    def window(self, tracer) -> float:
        """Offer the traffic for the window; return its length in seconds:
        from its start to the end of the last tick begun inside it."""
        import jax

        T = self.ctx.seconds
        loop = self.wl["loop"]
        t0 = time.perf_counter()
        tracer.arm(t0)
        if loop["kind"] == "open":
            with jax.profiler.TraceAnnotation("make_requests"):
                due = self.traffic.schedule()
                pending = []
                for t in due:
                    pending.extend(self.requests(self.traffic.mix_at(t), 1,
                                                 due=t))
            k = 0
            while True:
                now = time.perf_counter() - t0
                if now >= T:
                    break
                j = k
                while j < len(pending) and pending[j].due <= now:
                    j += 1
                if j == k:
                    nxt = pending[k].due if k < len(pending) else T
                    time.sleep(max(0.0, min(nxt, T) - now))
                    continue
                tracer.poll(now)
                self.tick(pending[k:j], t0, pending[k].mix)
                k = j
            end = max(T, time.perf_counter() - t0)
            for r in pending[k:]:
                if r.due < end:
                    self.served.append(r)
        else:
            now = 0.0
            while now < T:
                tracer.poll(now)
                # the profiler's stop holds the host for tens of seconds:
                # the next tick's mix is the one due when it begins
                now = time.perf_counter() - t0
                if now >= T:
                    break
                mix = self.traffic.mix_at(now)
                with jax.profiler.TraceAnnotation("make_requests"):
                    reqs = self.requests(mix, int(loop["clients"]), due=now)
                self.tick(reqs, t0, mix)
                now = time.perf_counter() - t0
            end = now
        tracer.finish()
        return end

    def free(self):
        """Drop the program's state so that the reference has the chip."""
        self.tuner = self.backend = None
        gc.collect()


def p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def run(ctx) -> dict:
    cell = ServeCell(ctx)
    begun = time.perf_counter()
    cell.build()
    built = time.perf_counter()
    cell.setup()
    ctx.end_setup()
    rep = cell.tuner.reports[0]
    print(f"bench: set-up: weights and tuner {built - begun:.3f} s, tune and "
          f"warm-ups {time.perf_counter() - built:.3f} s; tuned "
          f"{dict(rep.config)} after {rep.live_trials} trials",
          file=sys.stderr)
    window_s = cell.window(ctx.tracer)
    ctx.read_memory()
    cell.free()
    used = sorted({(t.mix, tuple(sorted(t.config.items())))
                   for t in cell.ticks})
    print(f"bench: window: {len(cell.ticks)} ticks; configurations "
          f"{[(mix, dict(c)) for mix, c in used]}", file=sys.stderr)

    answered = [r for r in cell.served if not math.isnan(r.done)]
    latencies = [(r.done if not math.isnan(r.done) else window_s) - r.due
                 for r in cell.served]
    tokens = sum(len(r.tokens) for r in answered)
    retunes = [t for t in cell.ticks if t.live_trials > 0]
    e2e = {
        "serve_p95_s": p95(latencies) if latencies else None,
        "serve_tokens_per_s": tokens / window_s,
        "retune_s": (sum(t.end - t.start for t in retunes) / len(retunes)
                     if retunes else None),
    }
    checks, failed = check.serve(ctx, answered)
    return {
        "attempted": len(cell.served),
        "failed": failed,
        "end_to_end": e2e,
        "checks": checks,
        "records": {"cell": cell, "window_s": window_s, "answered": answered,
                    "retunes": retunes},
    }


def traced_ticks(ctx, res):
    """(tick, its start and end in trace ns) of every tick that ran whole
    inside the traced slice of the window."""
    tr, tracer = res.get("trace"), ctx.tracer
    if tr is None:
        return []

    def ns(t):
        return tr.lo + (t - tracer.on) * 1e9

    return [(t, ns(t.start), ns(t.end)) for t in res["records"]["cell"].ticks
            if t.start >= tracer.on and t.end <= tracer.off]
