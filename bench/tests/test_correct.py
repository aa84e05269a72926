"""``correct`` on a tiny serving cell run whole on the CPU: the sound program
passes, and each fault a serving cell can have, planted in the timed path,
turns it false: a token altered where it is produced, a decode step that
returns its cache unchanged, and half of each wave left out."""
import jax.numpy as jnp
import pytest

import tiny
from repro.models.registry import Model
from repro.serve.engine import ServeEngine


def altered_token(orig):
    def decode(self, params, cache, batch):
        logits, new = orig(self, params, cache, batch)
        return jnp.roll(logits, 1, axis=-1), new
    return decode


def unchanged_state(orig):
    def decode(self, params, cache, batch):
        logits, _ = orig(self, params, cache, batch)
        return logits, cache
    return decode


def half_of_the_wave(orig):
    """Each wave computes its first half; the other rows get those answers,
    cut or repeated to their own lengths."""
    def run_wave(self, wave):
        half = wave[:max(1, len(wave) // 2)]
        out = orig(self, half)
        for i, r in enumerate(wave[len(half):]):
            src = out[half[i % len(half)].uid] or [0]
            out[r.uid] = (src * r.max_new_tokens)[:r.max_new_tokens]
        return out
    return run_wave


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_sound_program_is_correct(kind):
    out = tiny.result(tiny.context(kind, seed=5))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [altered_token, unchanged_state,
                                   half_of_the_wave])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    if fault is half_of_the_wave:
        monkeypatch.setattr(ServeEngine, "_run_wave",
                            fault(ServeEngine._run_wave))
    else:
        monkeypatch.setattr(Model, "decode", fault(Model.decode))
    out = tiny.result(tiny.context("closed", seed=5))
    assert not out["correct"]
    c = out["checks"]
    assert c["sampled_tokens"]["value"] >= c["sampled_tokens"]["limit"]
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]


def test_traced_run_reports_per_layer_metrics():
    ctx = tiny.context("closed", seed=5, trace=True)
    out = tiny.result(ctx)
    assert out["correct"], out["checks"]
    names = {m["name"] for m in ctx.bench["per_layer"]}
    assert set(out["metrics"]) <= names
    # the CPU has no device plane: the readers of the device trace return
    # nothing, and those of the program's counters still read
    assert {"serve_compile_s", "live_trials_per_retune",
            "trial_wave_s"} <= set(out["metrics"])
    assert out["metrics"]["live_trials_per_retune"]["value"] > 0
    assert out["device"]["window_s"] > 0 and "breakdown" in out
    assert list(out)[-1] == "checks"
