"""A tiny serving cell that runs a whole benchmark run on the CPU."""
import copy
import json
import os

from bench import run

HERE = os.path.dirname(os.path.abspath(__file__))

CONFIG = {
    "reference": "qwen2", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16", "vocab_size": 1000,
}
CHAT = {"prompt_len": 16,
        "answer": {"median": 4, "sigma": 0.5, "min": 2, "max": 6}}
DOCS = {"prompt_len": 40,
        "answer": {"median": 3, "sigma": 0.3, "min": 2, "max": 4}}
WORKLOADS = {
    "open": {
        "driver": "serve", "loop": {"kind": "open", "rate_per_s": 8.0},
        "phases": [{"from": 0.0, "mix": "chat"}], "mixes": {"chat": CHAT},
    },
    "closed": {
        "driver": "serve", "loop": {"kind": "closed", "clients": 4},
        "phases": [{"from": 0.0, "mix": "chat"}, {"from": 0.3, "mix": "docs"},
                   {"from": 0.6, "mix": "chat"}],
        "mixes": {"chat": CHAT, "docs": DOCS},
    },
}
COMMON = {
    "setup_mix": "chat",
    "tuner": {"max_live_trials": 4, "calib_n": 4, "window": 4, "seed": 0},
    "bucketer": {"max_prompt": 64, "max_new": 2},
    "space": {"batch_sizes": [2, 4], "max_seqs": [32, 48, 64]},
    "check": {"tokens": 24, "max_logit_gap": 0.05},
    "trace": {"seconds": 1.0},
}


def bench_json(kind: str) -> dict:
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench = copy.deepcopy(bench)
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["workloads"] = [{"name": kind, "config": "tiny", "traffic": kind,
                           "chips": 1, "why": "test"}]
    return bench


class FakeChip:
    """The CPU, named as the first chip of ``bench/peaks.json``."""

    platform = "tpu"

    def __init__(self):
        with open(os.path.join(HERE, "..", "peaks.json")) as f:
            self.device_kind = next(iter(json.load(f)["kinds"]))

    def memory_stats(self):
        return None


def context(kind: str, seed: int = 3, seconds: float = 2.0,
            trace: bool = False, **overrides):
    from unittest import mock

    wl = copy.deepcopy({**WORKLOADS[kind], **COMMON})
    wl.update(overrides)
    ctx = run.Context(bench_json(kind), kind, wl, dict(CONFIG), seed,
                      seconds, trace)
    with mock.patch("jax.devices", lambda *a: [FakeChip()]):
        ctx.attach_device()
    return ctx


def result(ctx) -> dict:
    return run.result(ctx, run.drive(ctx))
