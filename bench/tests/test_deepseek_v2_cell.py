"""A tiny closed-loop cell through the ``deepseek_v2`` family, run whole on
the CPU: it is correct against its reference, its engine moves the expert
counters ``moe.pairs_held`` and ``moe.experts_touched``, and a traced run
reads ``expert_rows_per_call`` from them."""
import copy
import json
import os
from unittest import mock

import pytest

import tiny
from bench import run
from repro import obs

ROOT = os.path.dirname(os.path.dirname(tiny.HERE))
with open(os.path.join(ROOT, "tests", "data", "deepseek_v2_tiny.json")) as f:
    CONFIG = json.load(f)       # float32: the CPU's dot has no bf16 x bf16
                                # into float32, which the latent decode asks


CELL = "serve.deepseek-v2-lite.docs"


def bench_json() -> dict:
    """The benchmark with this cell alone, its metrics' lists kept: a run
    reads the metrics listed for the cell and those listed for none."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": CELL, "config": "tiny",
                           "traffic": "closed", "chips": 1, "why": "test"}]
    return bench


def context(seed, trace=False):
    wl = copy.deepcopy({**tiny.WORKLOADS["closed"], **tiny.COMMON})
    # a window of several ticks even on a busy CPU, so that the check's
    # sample holds its tokens
    ctx = run.Context(bench_json(), CELL, wl, dict(CONFIG), seed, 8.0, trace)
    with mock.patch("jax.devices", lambda *a: [tiny.FakeChip()]):
        ctx.attach_device()
    return ctx


@pytest.mark.parametrize("seed", [5, 2147515104])
def test_tiny_cell_is_correct_and_counts_its_experts(seed):
    before = obs.snapshot()
    out = tiny.result(context(seed))
    after = obs.snapshot()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    for name in ("moe.pairs_held", "moe.experts_touched"):
        assert after.get(name, 0) > before.get(name, 0)


def test_traced_run_reads_expert_rows_per_call():
    out = tiny.result(context(5, trace=True))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) <= {"decode_step_ms.moe",
                                   "decode_hbm_roofline.moe",
                                   "expert_rows_per_call"}
    rows = out["metrics"]["expert_rows_per_call"]["value"]
    # a tiny wave of at most 4 rows, top-4 of 16 experts, 8 held: each
    # touched expert takes from 1 to 4 of the wave's rows
    assert 1.0 <= rows <= 4.0
    # the CPU has no device plane: the device readers return nothing
    assert "decode_step_ms.moe" not in out["metrics"]
    assert "decode_hbm_roofline.moe" not in out["metrics"]


# -- the readers on a hand-made ring and trace ------------------------------
T0, ON, OFF = 100.0, 1.0, 1.5   # the window's perf_counter; traced slice, s
LO = 1_000_000                  # the trace's ns at ``traced``
P0 = round((T0 + ON) * 1e9)     # the ring's ns at ``traced``


def reader_case(monkeypatch, calls, counts):
    from types import SimpleNamespace as NS

    from bench import lm, trace
    from bench.drivers.serve import Wave

    r = obs.Recorder()
    r.ring.append(obs.Span(1, -1, "tuner.tick", P0, P0 + 50_000_000, {},
                           counts))
    r.counters.update(counts)
    monkeypatch.setattr(obs, "spans", r.spans)
    monkeypatch.setattr(obs, "snapshot", r.snapshot)
    ctx = NS(tracer=NS(t0=T0, on=ON, off=OFF),
             config=lm.load_config("deepseek-v2-lite"),
             peaks={"hbm_bytes_per_s": 819e9})
    # one tick of one wave: two rows, a 10-token prompt, answers of 3
    tick = NS(start=ON, end=ON + 0.05, waves=[Wave([3, 3], 10)])
    dev = "/device:TPU:0"
    tr = trace.Trace({"ops": {dev: []}, "host": [["traced", LO, 10**9]],
                      "programs": {dev: [[f"jit_decode({i})",
                                          LO + 1_000_000 + i * 6_000_000,
                                          5_000_000] for i in range(calls)]}},
                     LO, LO + 10**9)
    return ctx, {"trace": tr, "records": {"cell": NS(ticks=[tick])}}


def test_decode_roofline_of_the_expert_model_by_hand(monkeypatch):
    from bench.families import deepseek_v2 as fam
    counts = {"moe.pairs_held": 30, "moe.experts_touched": 12}
    ctx, res = reader_case(monkeypatch, 2, counts)
    m = fam.dims(ctx.config)
    need = (fam.decode_bytes(m, [10, 10]) + fam.decode_bytes(m, [11, 11])
            + 12 * fam.expert_bytes(m))
    got = run.read_metric("decode_hbm_roofline.moe", ctx, res)
    assert got == pytest.approx(100 * need / 819e9 / 0.010)
    assert run.read_metric("expert_rows_per_call", ctx, res) == 2.5
    assert run.read_metric("decode_step_ms.moe", ctx, res) == 5.0


def test_readers_without_a_reading(monkeypatch):
    counts = {"moe.pairs_held": 30, "moe.experts_touched": 12}
    # the trace holds a call the waves do not make
    ctx, res = reader_case(monkeypatch, 3, counts)
    assert run.read_metric("decode_hbm_roofline.moe", ctx, res) is None
    # a program that does not count its experts
    ctx, res = reader_case(monkeypatch, 2, {"engine.decode_steps": 2})
    assert run.read_metric("decode_hbm_roofline.moe", ctx, res) is None
    assert run.read_metric("expert_rows_per_call", ctx, res) is None
