"""The tiny closed-loop ``deepseek_v2`` cell run whole on the CPU, for what
its engine says of the MoE layers read in place: every decode call moves
``moe.inplace_layers``, and a traced run reads ``moe_inplace_share`` as 100.
A tiny ``qwen2`` cell, which has no MoE layer, reads nothing there even where
the process holds the counter."""
import copy
import json
import os
from unittest import mock

import tiny
from bench import run
from repro import obs

ROOT = os.path.dirname(os.path.dirname(tiny.HERE))
with open(os.path.join(ROOT, "tests", "data", "deepseek_v2_tiny.json")) as f:
    CONFIG = json.load(f)

CELL = "serve.deepseek-v2-lite.docs"


def context(seed, trace=False):
    """The cell's run at the tiny configuration, its metrics' lists kept."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": CELL, "config": "tiny",
                           "traffic": "closed", "chips": 1, "why": "test"}]
    wl = copy.deepcopy({**tiny.WORKLOADS["closed"], **tiny.COMMON})
    ctx = run.Context(bench, CELL, wl, dict(CONFIG), seed, 8.0, trace)
    with mock.patch("jax.devices", lambda *a: [tiny.FakeChip()]):
        ctx.attach_device()
    return ctx


def test_traced_run_reads_every_layer_in_place():
    before = obs.snapshot()
    out = tiny.result(context(2147515111, trace=True))
    after = obs.snapshot()
    assert out["correct"], out["checks"]
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("moe.inplace_layers", "engine.decode_steps")}
    assert moved["engine.decode_steps"] > 0
    # every decode call, of at most 4 rows, reads each of the tiny model's
    # MoE layers (one dense layer comes first) in place
    layers = CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"]
    assert moved["moe.inplace_layers"] == layers * moved[
        "engine.decode_steps"]
    assert out["metrics"]["moe_inplace_share"]["value"] == 100.0


def test_model_without_moe_layers_reads_nothing():
    obs.add("moe.inplace_layers", 1)    # as an expert model would move it
    out = tiny.result(tiny.context("closed", seed=5, trace=True))
    assert out["correct"], out["checks"]
    assert "moe_inplace_share" not in out["metrics"]
