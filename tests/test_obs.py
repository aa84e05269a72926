"""repro.obs: span nesting and parents, self time, the ring's bound,
counters, the compile listener, agreement with the profiler's own host
events, and the serve engine's counts."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_serve import EchoModel

from repro import obs
from repro.core.hwspec import SPECS
from repro.serve.autotune import (EngineBackend, OnlineAutotuner,
                                  ServeWorkloadStats, ShapeBucketer,
                                  serve_space)
from repro.serve.engine import Request, ServeEngine


def by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def test_nesting_parents_scope_ids_and_self_time():
    rec = obs.Recorder()
    with rec.span("tuner.tick", counts=True):
        with rec.span("tuner.retune"):
            time.sleep(0.002)
            with rec.span("tuner.trial"):
                with rec.span("engine.wave", uids=[4, 5]):
                    with rec.span("engine.step"):
                        pass
                time.sleep(0.002)
    with rec.span("engine.wave"):
        pass
    spans = by_name(rec.spans())
    tick, = spans["tuner.tick"]
    retune, = spans["tuner.retune"]
    trial, = spans["tuner.trial"]
    step, = spans["engine.step"]
    inner, outer = spans["engine.wave"]
    assert tick.parent == -1 and tick.attrs == {} and tick.counts == {}
    assert retune.parent == tick.id and trial.parent == retune.id
    assert inner.parent == trial.id and inner.attrs == {"uids": [4, 5]}
    assert step.parent == inner.id and step.counts is None
    assert outer.parent == -1
    assert len({r.id for r in rec.spans()}) == 6
    assert all(r.start <= r.end for r in rec.spans())
    assert tick.start <= retune.start <= trial.start <= trial.end \
        <= retune.end <= tick.end
    recs = rec.spans()
    assert obs.self_ns(retune, recs) == retune.ns - trial.ns >= 2e6
    assert obs.self_ns(retune, recs, ("tuner.trial",)) == retune.ns - trial.ns
    assert obs.self_ns(retune, recs, ("engine.wave",)) == retune.ns
    assert obs.self_ns(trial, recs) == trial.ns - inner.ns
    assert obs.self_ns(step, recs) == step.ns
    # the interval query keeps whole records only
    lo, hi = trial.start * 1e-9, trial.end * 1e-9
    assert {r.name for r in rec.spans(lo, hi)} == {
        "tuner.trial", "engine.wave", "engine.step"}


def test_ring_keeps_the_newest_and_counts_what_it_drops():
    rec = obs.Recorder(size=4)
    for i in range(10):
        with rec.span("engine.step", i=i):
            pass
    assert [r.attrs["i"] for r in rec.spans()] == [6, 7, 8, 9]
    assert rec.snapshot()["obs.dropped"] == 6
    assert obs.RING_SIZE == obs.Recorder().ring.maxlen


def test_counters_and_their_moves_over_scope_spans():
    rec = obs.Recorder()
    rec.add("engine.host_pulls", 3)
    with rec.span("tuner.tick", counts=True):
        with rec.span("engine.wave", counts=True):
            rec.add("engine.decode_steps")
            rec.add("engine.host_pulls", 4)
            with rec.span("engine.step"):
                rec.add("engine.decode_steps")
        rec.add("engine.host_pulls", 1)
    snap = rec.snapshot()
    assert snap["engine.decode_steps"] == 2
    assert snap["engine.host_pulls"] == 8
    spans = by_name(rec.spans())
    assert spans["engine.step"][0].counts is None
    assert spans["engine.wave"][0].counts == {"engine.decode_steps": 2,
                                              "engine.host_pulls": 4}
    assert spans["tuner.tick"][0].counts == {"engine.decode_steps": 2,
                                             "engine.host_pulls": 5}
    # a snapshot is a copy
    snap["engine.host_pulls"] = 0
    assert rec.snapshot()["engine.host_pulls"] == 8


def test_compile_listener_splits_by_function_name():
    def obs_probe_a(x):
        return x * 2 + 1

    def obs_probe_b(x):
        return jnp.sin(x) - x

    before = obs.snapshot()
    x = jnp.arange(7.0)
    jax.jit(obs_probe_a)(x).block_until_ready()
    jax.jit(obs_probe_b)(x).block_until_ready()
    after = obs.snapshot()
    jax.jit(obs_probe_b)(x + 1).block_until_ready()     # no new compile
    last = obs.snapshot()
    for f in ("jit(obs_probe_a)", "jit(obs_probe_b)"):
        assert after["compile.seconds"][f] > before["compile.seconds"].get(
            f, 0.0)
    assert last["compile.seconds"]["jit(obs_probe_b)"] \
        == after["compile.seconds"]["jit(obs_probe_b)"]
    assert after.get("compile.cache_hits", 0) >= before.get(
        "compile.cache_hits", 0)


def reqs(plen, answers, uid0=0):
    return [Request(uid=uid0 + i, prompt=np.ones(plen, np.int32),
                    max_new_tokens=a) for i, a in enumerate(answers)]


def test_profile_holds_every_program_span_on_the_rings_clock(tmp_path):
    from jax.profiler import ProfileData

    backend = EngineBackend(EchoModel(), seq_round=16)
    tuner = OnlineAutotuner(
        backend, bucketer=ShapeBucketer(max_prompt=8, max_new=4),
        space=serve_space(batch_sizes=(1, 2), max_seqs=(16, 32)),
        hw=SPECS["tpu_v5e"],
        stats=ServeWorkloadStats(param_bytes=1e6, d_model=32, n_layers=2),
        max_live_trials=2, seed=0)
    backend.serve({"BATCH": 2, "MAX_SEQ": 16}, reqs(4, [2, 2]))  # compile
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("traced"):
            anchor_s = time.perf_counter()
            tuner.serve(reqs(4, [3, 2, 2, 1]))
            end_s = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    traced_ns = events["traced"][0][0]
    spans = by_name(obs.spans(anchor_s, end_s))
    assert set(spans) == {"tuner.tick", "tuner.retune", "tuner.trial",
                          "engine.wave", "engine.prefill", "engine.step",
                          "engine.pull"}
    worst = 0.0
    for name, recs in spans.items():
        marks = sorted(events[name])
        assert len(marks) == len(recs), name
        for r, (s, e) in zip(sorted(recs, key=lambda r: r.start), marks):
            worst = max(worst,
                        abs(obs.trace_ns(r.start, (anchor_s, traced_ns)) - s),
                        abs(obs.trace_ns(r.end, (anchor_s, traced_ns)) - e))
    assert worst <= 50e3, f"{worst * 1e-3:.1f} us"


def test_engine_counts_decode_calls_and_token_reads(monkeypatch):
    """One transfer of the token vector a pass, no per-row ``int()`` read,
    and every decode of a wave without EOS dispatched before its pass's
    read."""
    from jax._src.array import ArrayImpl

    answers = [5, 3, 1]
    engine = ServeEngine(EchoModel(), batch_size=4, max_seq=32,
                         rng=jax.random.PRNGKey(0))
    engine.generate(reqs(4, [2, 2, 2]))                     # compile
    calls, ints, transfers = [], [], []
    orig_decode, orig_get = engine._decode, jax.device_get
    orig_int = ArrayImpl.__int__

    def decode(*a):
        calls.append(1)
        return orig_decode(*a)

    def to_int(self):
        ints.append(1)
        return orig_int(self)

    def device_get(x):
        transfers.append(x.shape)
        return orig_get(x)

    engine._decode = decode
    monkeypatch.setattr(ArrayImpl, "__int__", to_int)
    monkeypatch.setattr(jax, "device_get", device_get)
    before = obs.snapshot()
    out = engine.generate(reqs(4, answers, uid0=10))
    after = obs.snapshot()
    monkeypatch.undo()
    assert [len(out[10 + i]) for i in range(3)] == answers

    def moved(name):
        return after[name] - before.get(name, 0)

    assert moved("engine.decode_steps") == len(calls) == max(answers) - 1
    assert moved("engine.host_pulls") == max(answers)
    assert transfers == [(3, 1)] * max(answers)
    assert ints == []
    assert moved("engine.decode_ahead") == max(answers) - 1
    wave, = [r for r in obs.spans() if r.name == "engine.wave"
             and r.attrs["uids"] == [10, 11, 12]]
    inside = [r for r in obs.spans() if r.parent == wave.id]
    assert sum(r.name == "engine.step" for r in inside) == max(answers)
    assert sum(r.name == "engine.prefill" for r in inside) == 1


def test_span_records_survive_an_exception():
    rec = obs.Recorder()
    with pytest.raises(ValueError):
        with rec.span("tuner.tick"):
            with rec.span("engine.step"):
                raise ValueError
    with rec.span("engine.wave"):
        pass
    spans = rec.spans()
    assert [r.name for r in spans] == ["engine.step", "tuner.tick",
                                       "engine.wave"]
    assert spans[0].parent == spans[1].id and spans[-1].parent == -1
