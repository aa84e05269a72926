"""Bit-for-bit pins of the serving caches, and the values the GQA cache holds.

For each family with a K/V cache (GQA with q/k/v bias, MLA with MoE, the
hybrid's shared attention, the encoder-decoder's self-attention), the smoke
model prefills an 8-token prompt into a 12-position cache and decodes two
tokens.  The shape, dtype and sha256 of every logit and cache leaf must equal
the pins in ``tests/data/cache_pins.json``.

Regenerate the pins (only for a change that means to alter these numbers):
``PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_cache_layout.py``.

The GQA cache stores ``g`` kv heads per row, (B, Hkv/g, S, g*D).  For each
GQA family, and for head geometries the smokes lack, the cache unpacked to
(B, S, Hkv, D) after a prefill and after each of two decodes must equal, bit
for bit, K and V of every position written so far (zeros beyond),
K and V being computed and rotated from each attention call's own input in
the same program; and each decode's logits must equal those of the same
step with its attention replaced by a plain float32 softmax attention over
the unpacked cache.
"""
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.models import attention
from repro.models.common import apply_rope, dense
from repro.models.registry import build_model

PINS = pathlib.Path(__file__).parent / "data" / "cache_pins.json"
FAMILIES = ("qwen1.5-0.5b", "deepseek-v2-236b", "zamba2-2.7b",
            "seamless-m4t-large-v2")
B, S, MAX = 2, 8, 12
GQA = ("qwen1.5-0.5b", "zamba2-2.7b", "seamless-m4t-large-v2")
# (n_heads, n_kv_heads, head_dim) on the qwen smoke: the smokes' 4 kv heads
# of 16 share one row; these give rows of two heads, head-major rows
# (g = 1) and rows that two heads fill to 96 of 128 lanes, with 2 queries
# per kv head
GEOMETRIES = {"rows-of-2": (8, 4, 64), "head-major": (4, 2, 128),
              "rows-of-96": (12, 6, 48)}


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


def _inputs(cfg):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (B, S + 2)).astype(np.int32)
    extra = {}
    if cfg.frontend == "audio":
        extra["frames"] = rng.standard_normal(
            (B, cfg.frontend_len, cfg.frontend_dim)).astype(np.float32)
    return toks, extra


def _record(name):
    cfg = SMOKES[name]
    m = build_model(cfg)
    params = _params(m)
    toks, extra = _inputs(cfg)
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


def _gqa_cases():
    cases = [pytest.param(SMOKES[n], id=n) for n in GQA]
    for tag, (h, hk, hd) in GEOMETRIES.items():
        cfg = SMOKES["qwen1.5-0.5b"].scaled(n_heads=h, n_kv_heads=hk,
                                             head_dim=hd)
        cases.append(pytest.param(cfg, id=f"qwen1.5-0.5b-{tag}"))
    return cases


def _kv_caches(cache):
    """Every GQA K/V cache of a model's cache tree: {path: (k, v)}."""
    out = {}
    for path, sub in jax.tree_util.tree_flatten_with_path(
            cache, is_leaf=lambda t: isinstance(t, dict) and "k" in t)[0]:
        if isinstance(sub, dict) and "pos" in sub:
            out[jax.tree_util.keystr(path)] = (sub["k"], sub["v"])
    return out


def _unpack(a, hd):
    """The stacked cache (L, B, Hkv/g, S, g*D) as (L, B, S, Hkv, D)."""
    n, b, rows, s, w = a.shape
    a = np.asarray(a).reshape(n, b, rows, s, w // hd, hd)
    return a.transpose(0, 1, 3, 2, 4, 5).reshape(n, b, s, rows * w // hd, hd)


def _rotated_kv(p, cfg, x, start):
    """K rotated at positions ``start``.. and V, (B, S, Hkv, D), from one
    attention call's input and weights."""
    b, s, _ = x.shape
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    k = dense(x, p["wk"], p.get("bk")).reshape(b, s, hk, hd)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, s, hk, hd)
    return apply_rope(k, (start + jnp.arange(s))[None, :], cfg.rope_theta), v


_REAL_GQA = attention.gqa_apply


def _probe(monkeypatch):
    """Record K and V of every causal GQA call, computed from its input in
    the same program, in the order the model makes the calls."""
    calls = []

    def probe(p, cfg, x, positions=None, cache=None, kv_chunk=1024):
        if cfg.causal:
            start = cache["pos"] if cache is not None else 0
            jax.debug.callback(lambda k, v: calls.append((k, v)),
                               *_rotated_kv(p, cfg, x, start), ordered=True)
        return _REAL_GQA(p, cfg, x, positions, cache, kv_chunk)

    monkeypatch.setattr(attention, "gqa_apply", probe)
    return calls


def _plain_gqa(p, cfg, x, positions=None, cache=None, kv_chunk=1024):
    """``gqa_apply`` whose decode attends with a plain float32 softmax over
    the unpacked cache and the token's own K and V."""
    if cache is None:
        return _REAL_GQA(p, cfg, x, positions, cache, kv_chunk)
    b = x.shape[0]
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = cache["pos"]
    q = apply_rope(dense(x, p["wq"], p.get("bq")).reshape(b, 1, h, hd),
                   pos[None, None], cfg.rope_theta)
    k = apply_rope(dense(x, p["wk"], p.get("bk")).reshape(b, 1, hk, hd),
                   pos[None, None], cfg.rope_theta)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, 1, hk, hd)

    def unpacked(a, new):                           # (B, S_max, Hkv, D)
        rows, t, w = a.shape[1:]
        a = a.reshape(b, rows, t, w // hd, hd).transpose(0, 2, 1, 3, 4)
        a = a.reshape(b, t, hk, hd)
        return jax.lax.dynamic_update_slice(a, new, (0, pos, 0, 0))

    keys = jnp.repeat(unpacked(cache["k"], k), h // hk, axis=2)
    vals = jnp.repeat(unpacked(cache["v"], v), h // hk, axis=2)
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("bhd,bthd->bht", q[:, 0], keys,
                        precision=hi) / np.sqrt(hd)
    scores = jnp.where(jnp.arange(keys.shape[1]) <= pos, scores, -jnp.inf)
    out = jnp.einsum("bht,bthd->bhd", jax.nn.softmax(scores, axis=-1), vals,
                     precision=hi)
    y = dense(out.reshape(b, 1, h * hd).astype(x.dtype), p["wo"])
    return y, {"k": attention._to_rows(cfg, k),
               "v": attention._to_rows(cfg, v)}


@pytest.mark.parametrize("g, geometry", [
    (4, (4, 16)), (2, (16, 64)), (1, (2, 128)), (1, (1, 256)),
    (2, (6, 48)), (1, (8, 80)), (8, (8, 16)), (1, (1, 64))])
def test_kv_group_fills_a_128_lane_row(g, geometry):
    hk, hd = geometry
    cfg = attention.AttnConfig(d_model=64, n_heads=hk, n_kv_heads=hk,
                               head_dim=hd)
    assert attention.kv_group(cfg) == g
    assert attention.attn_init_cache(cfg, 2, 12, jnp.float32)["k"].shape \
        == (2, hk // g, 12, g * hd)


@pytest.mark.parametrize("cfg", _gqa_cases())
def test_gqa_cache_holds_the_rotated_kv(cfg, monkeypatch):
    calls = _probe(monkeypatch)
    m = build_model(cfg)
    params = _params(m)
    toks, extra = _inputs(cfg)
    _, cache = m.prefill(params, {"tokens": toks[:, :S], **extra},
                         max_seq=MAX)
    caches = [cache]
    for t in range(2):
        _, cache = m.decode(params, cache,
                            {"tokens": toks[:, S + t:S + t + 1]})
        caches.append(cache)
    jax.effects_barrier()
    hd = cfg.eff_head_dim
    paths = sorted(_kv_caches(cache))
    layers = [_kv_caches(caches[0])[path][0].shape[0] for path in paths]
    assert len(calls) == 3 * sum(layers)
    want = {path: np.zeros((2, n, B, MAX, cfg.n_kv_heads, hd), np.float32)
            for path, n in zip(paths, layers)}
    done = iter(calls)
    for step, cache in enumerate(caches):
        first, span = (0, S) if step == 0 else (S + step - 1, 1)
        for path in paths:                          # the models' call order
            for layer in range(want[path].shape[1]):
                want[path][:, layer, :, first:first + span] = next(done)
        for path, (k, v) in _kv_caches(cache).items():
            for got, ref in zip((k, v), want[path]):
                got = _unpack(got, hd)
                assert got.dtype == np.float32
                np.testing.assert_array_equal(got, ref, err_msg=path)


@pytest.mark.parametrize("cfg", _gqa_cases())
def test_gqa_decode_matches_a_plain_attention(cfg, monkeypatch):
    m = build_model(cfg)
    params = _params(m)
    toks, extra = _inputs(cfg)
    _, cache = m.prefill(params, {"tokens": toks[:, :S], **extra},
                         max_seq=MAX)
    for t in range(2):
        batch = {"tokens": toks[:, S + t:S + t + 1]}
        with monkeypatch.context() as mp:
            mp.setattr(attention, "gqa_apply", _plain_gqa)
            want, _ = m.decode(params, cache, batch)
        got, cache = m.decode(params, cache, batch)
        gap = np.max(np.abs(np.asarray(got) - np.asarray(want)))
        assert gap <= 1e-5 * np.max(np.abs(np.asarray(want))), (t, gap)


if __name__ == "__main__":
    PINS.write_text(json.dumps({n: _record(n) for n in FAMILIES}, indent=1,
                               sort_keys=True) + "\n")
