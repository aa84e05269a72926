"""Bit-for-bit pins of the serving caches.

For each family with a K/V cache (GQA with q/k/v bias, MLA with MoE, the
hybrid's shared attention, the encoder-decoder's self-attention), the smoke
model prefills an 8-token prompt into a 12-position cache and decodes two
tokens.  The shape, dtype and sha256 of every logit and cache leaf must equal
the pins in ``tests/data/cache_pins.json``.

Regenerate the pins (only for a change that means to alter these numbers):
``PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_cache_layout.py``.
"""
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.models.registry import build_model

PINS = pathlib.Path(__file__).parent / "data" / "cache_pins.json"
FAMILIES = ("qwen1.5-0.5b", "deepseek-v2-236b", "zamba2-2.7b",
            "seamless-m4t-large-v2")
B, S, MAX = 2, 8, 12


def _params(m):
    """Initialised parameters; the zero-initialised leaves (the q/k/v
    biases) get small random values so that the pins depend on them."""
    leaves, tree = jax.tree.flatten(m.init(jax.random.PRNGKey(0)))
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [a if bool(a.any()) else
              0.02 * jax.random.normal(k, a.shape, a.dtype)
              for k, a in zip(keys, leaves)]
    return jax.tree.unflatten(tree, leaves)


def _digest(tree):
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        digest = hashlib.sha256(a.tobytes()).hexdigest()
        out[jax.tree_util.keystr(path)] = [list(a.shape), str(a.dtype), digest]
    return out


def _record(name):
    cfg = SMOKES[name]
    m = build_model(cfg)
    params = _params(m)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (B, S + 2)).astype(np.int32)
    extra = {}
    if cfg.frontend == "audio":
        extra["frames"] = rng.standard_normal(
            (B, cfg.frontend_len, cfg.frontend_dim)).astype(np.float32)
    logits, cache = m.prefill(params, {"tokens": toks[:, :S], **extra},
                              max_seq=MAX)
    steps = {"prefill": {"logits": logits, "cache": cache}}
    for t in range(2):
        logits, cache = m.decode(params, cache,
                                 {"tokens": toks[:, S + t:S + t + 1]})
        steps[f"decode{t + 1}"] = {"logits": logits, "cache": cache}
    return _digest(steps)


@pytest.mark.parametrize("name", FAMILIES)
def test_cache_and_logits_match_the_pins(name):
    want = json.loads(PINS.read_text())[name]
    got = _record(name)
    assert sorted(got) == sorted(want)
    differ = [k for k in want if got[k] != want[k]]
    assert not differ, f"{name}: {differ}"


if __name__ == "__main__":
    PINS.write_text(json.dumps({n: _record(n) for n in FAMILIES}, indent=1,
                               sort_keys=True) + "\n")
