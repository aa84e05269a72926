"""A configuration's model mapping comes from ``bench/families/<reference>.py``.

The Qwen2 family gives the same weights and parameter counts as the code it
was moved from (``data/weights_pin.json``, recorded from ``bench/lm.py``
before the move); a configuration whose ``reference`` names another family
module runs a whole cell through it; and one whose family module is missing
is refused with the file's name."""
import json
import os
import sys

import jax
import numpy as np
import pytest

import tiny
from bench import lm
from bench.families import qwen2
from bench.references import qwen2 as qwen2_reference

with open(os.path.join(tiny.HERE, "data", "weights_pin.json")) as f:
    PIN = json.load(f)


def checksum(leaf):
    """The float32 values' sum and position-weighted sum, in float64."""
    v = np.asarray(jax.device_get(leaf)).astype(np.float32).ravel()
    v = v.astype(np.float64)
    w = (np.arange(v.size) % 251 + 1).astype(np.float64)
    return [float(v.sum()), float((v * w).sum())]


@pytest.mark.parametrize("key", sorted(PIN["weights"]))
def test_weights_equal_those_of_the_code_before_the_move(key):
    seed, layout = key.split("/")
    cfg = dict(tiny.CONFIG)
    padded = lm.arch("tiny", cfg).padded_vocab if layout == "program" else 0
    w = lm.make_weights(cfg, int(seed), layout, padded)
    got = {jax.tree_util.keystr(p): [list(np.shape(a)), str(a.dtype)]
           + checksum(a)
           for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == PIN["weights"][key]


@pytest.mark.parametrize("name", sorted(PIN["param_count"]))
def test_param_count_equals_that_of_the_code_before_the_move(name):
    assert lm.param_count(lm.load_config(name)) == PIN["param_count"][name]


def test_a_family_enters_by_its_reference(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.families.toy", qwen2)
    monkeypatch.setitem(sys.modules, "bench.references.toy",
                        qwen2_reference)
    ctx = tiny.context("open", seed=6)
    ctx.config["reference"] = "toy"
    assert lm.family(ctx.config) is qwen2
    out = tiny.result(ctx)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_a_missing_family_names_its_file():
    cfg = {**tiny.CONFIG, "reference": "no_such_family"}
    for call in (lm.dims, lm.param_count, lambda c: lm.arch("x", c),
                 lambda c: lm.make_weights(c, 1)):
        with pytest.raises(ValueError,
                           match="bench/families/no_such_family.py"):
            call(cfg)
