"""DeepSeek-V2 on the program against its plain reference, on the CPU.

The configuration family ``bench/families/deepseek_v2.py`` maps a
DeepSeek-V2 ``config.json`` onto the program (latent attention with a normed
latent and YaRN, leading dense layers, unnormalised top-k gates, a chip's
share of the routed experts); ``bench/references/deepseek_v2.py`` is the
plain float32 reference the benchmark's check compares with.  Here, at a
tiny size: the program's prefill and decode through the cache give the
reference's logits, and each of those mechanisms, put back to its plain
alternative, breaks that; the rotations agree; the expert shares add up to
the uncut layer; the held experts' dense path (read in place) and grouped
path agree, and a layer takes the dense one up to its token bound; the YaRN numbers are those of the published formulas; the
seeded weights and the parameter counts are pinned; the tuner's cache bytes
per position and the engine's expert counters come from the model.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import lm
from bench.references import deepseek_v2 as reference
from repro import obs
from repro.models.common import apply_rope, init_params, mlp_apply
from repro.models.moe import (DENSE_MAX_TOKENS, MoEConfig, held_dense,
                              held_ragged, moe_apply, moe_apply_dropless,
                              moe_defs, route)
from repro.models.registry import build_model
from repro.serve.autotune import stats_from_model
from repro.serve.engine import Request, ServeEngine

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "deepseek_v2_tiny.json")) as f:
    TINY = json.load(f)
with open(os.path.join(HERE, "data", "deepseek_v2_pin.json")) as f:
    PIN = json.load(f)

# Both sides compute in float32 (the reference at ``highest`` precision):
# they differ only in the order of float32 sums (the absorbed decode, the
# chunked online softmax), about 1e-6 of the largest logit.  Each plain
# alternative below moves the logits by a tenth of the largest and more.
TOL = 1e-4
PROMPT, STEPS = 12, 3
PLAIN = {"rope_yarn": None, "mla_latent_norm": False, "moe_norm_topk": True}


def tokens():
    return np.random.default_rng(0).integers(
        1, TINY["vocab_size"], PROMPT + STEPS).astype(np.int32)


def program_logits(arch, params):
    """Last-position logits of the prefill, then of each decode step."""
    model = build_model(arch)
    toks = tokens()
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(
        toks[None, :PROMPT])}, max_seq=PROMPT + STEPS + 1)
    out = [logits[0, -1]]
    for t in range(STEPS):
        logits, cache = model.decode(params, cache, {"tokens": jnp.asarray(
            toks[None, PROMPT + t:PROMPT + t + 1])})
        out.append(logits[0, -1])
    return np.asarray(jnp.stack(out))[:, :TINY["vocab_size"]]


def relative_gap(arch, params):
    want = reference.logits(lm.dims(TINY), lm.make_weights(TINY, 3, "hf"),
                            tokens(),
                            np.arange(PROMPT - 1, PROMPT + STEPS))
    got = program_logits(arch, params)
    return float(np.abs(got - want).max() / np.abs(want).max())


def tiny_program(**changes):
    arch = lm.arch("tiny", TINY).scaled(**changes)
    params = lm.make_weights(TINY, 3, "program", arch.padded_vocab)
    if not arch.mla_latent_norm:
        for stack in ("dense_blocks", "blocks"):
            del params[stack]["attn"]["kv_ln"]
    return arch, params


def test_prefill_then_decode_match_the_reference():
    assert relative_gap(*tiny_program()) < TOL


@pytest.mark.parametrize("field", sorted(PLAIN))
def test_each_plain_alternative_fails_the_comparison(field):
    assert relative_gap(*tiny_program(**{field: PLAIN[field]})) > 100 * TOL


def test_interleaved_rotation_is_the_published_one_without_permutation():
    cfg = lm.load_config("deepseek-v2-lite")
    m = lm.dims(cfg)
    yarn = lm.arch("deepseek-v2-lite", cfg).rope_yarn
    t, d = 512, m["rope"]
    rng = np.random.default_rng(1)
    q = rng.standard_normal((t, 2, d)).astype(np.float32)
    k = rng.standard_normal((t, 1, d)).astype(np.float32)
    pos = jnp.arange(t)[None]
    pq = apply_rope(jnp.asarray(q)[None], pos, m["theta"], yarn)[0]
    pk = apply_rope(jnp.asarray(k)[None], pos, m["theta"], yarn)[0]
    rq, rk = reference.rope(jnp.asarray(q), m), reference.rope(
        jnp.asarray(k), m)
    # the published rotation returns the de-interleaved columns ...  (the
    # two compute their float32 frequencies in another order, one rounding
    # apart, so angles of positions up to 511 differ by up to 511 * 2^-24)
    halves = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    np.testing.assert_allclose(rq, pq[..., halves], atol=1e-4)
    # ... so q.k, all attention reads, is the same with no permutation (64
    # such products of values of about 1)
    np.testing.assert_allclose(jnp.einsum("thd,shd->hts", pq, pk),
                               jnp.einsum("thd,shd->hts", rq, rk),
                               atol=1e-3)


def test_yarn_frequencies_and_softmax_factor_by_hand():
    cfg = lm.load_config("deepseek-v2-lite")
    yarn = lm.arch("deepseek-v2-lite", cfg).rope_yarn
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    inv = yarn.inv_freq(64, 10000.0)
    # with factor 40 over 4096 and betas 32, 1: the correction range is
    # [10, 23] of the 32 frequencies
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(inv[11:23] < plain[11:23])
    assert np.all(inv[11:23] > plain[11:23] / 40)
    np.testing.assert_allclose(inv, reference.inv_freq(lm.dims(cfg)),
                               rtol=1e-6)
    assert yarn.rotary_scale == 1.0
    assert yarn.softmax_factor == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2, rel=1e-12)
    assert yarn.softmax_factor == pytest.approx(1.5896, abs=1e-4)


def held_part(helper, p, cfg, x):
    """The held experts' part of ``x`` (B, S, D) through one path's helper:
    (out, counts)."""
    xt = x.reshape(-1, x.shape[-1])
    _, gate_vals, gate_idx = route(p, cfg, xt)
    out, counts = helper(p, cfg, xt, gate_vals, gate_idx)
    return out.reshape(x.shape), counts


def moe_case(cfg, p, x, path):
    """(out, routed pairs of the held experts or None) of one path."""
    if path == "dropless":
        out, counts = moe_apply_dropless(p, cfg, x)
        return out, int(counts[0])
    if path == "ragged":
        out, counts = held_part(held_ragged, p, cfg, x)
        return out + mlp_apply(p["shared"], x), int(counts[0])
    return moe_apply(p, cfg, x)[0], None


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("path", ["dropless", "capacity", "ragged"])
def test_expert_shares_add_up_to_the_uncut_layer(path, norm_topk):
    """``dropless`` takes ``held_dense`` at these 16 tokens; ``ragged``
    reaches the grouped products through their own helper."""
    whole = MoEConfig(d_model=32, d_ff=16, n_experts=16, top_k=6, n_shared=2,
                      norm_topk=norm_topk)
    p = init_params(jax.random.PRNGKey(0), moe_defs(whole), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.float32)
    shared = mlp_apply(p["shared"], x)
    total = -7 * shared
    pairs = 0
    for i in range(8):
        cfg = whole._replace(share=(2 * i, 2))
        part = {**p, **{k: p[k][2 * i:2 * i + 2]
                        for k in ("w_gate", "w_up", "w_down")}}
        out, held_pairs = moe_case(cfg, part, x, path)
        total = total + out
        if held_pairs is not None:
            pairs += held_pairs
    np.testing.assert_allclose(total, moe_case(whole, p, x, path)[0],
                               atol=1e-5)
    if path != "capacity":
        assert pairs == 2 * 8 * 6      # every (token, expert) pair, once


# (experts, top-k, held share, gates renormalised): DeepSeek-V2-Lite's
# share of 8 of 64 at top-6 with softmax gates, and every expert held at
# top-1 (Llama-4-Scout's routing)
HELD_CASES = {"share-8-of-64-top-6": (64, 6, (8, 8), False),
              "all-held-top-1": (16, 1, (), True)}


@pytest.mark.parametrize("tokens", [16, 200])
@pytest.mark.parametrize("case", sorted(HELD_CASES))
def test_dense_and_ragged_paths_agree(case, tokens):
    n_experts, top_k, share, norm_topk = HELD_CASES[case]
    cfg = MoEConfig(d_model=32, d_ff=16, n_experts=n_experts, top_k=top_k,
                    norm_topk=norm_topk, share=share)
    p = init_params(jax.random.PRNGKey(0), moe_defs(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, 32),
                          jnp.float32)
    dense, dense_counts = held_part(held_dense, p, cfg, x)
    ragged, ragged_counts = held_part(held_ragged, p, cfg, x)
    np.testing.assert_allclose(dense, ragged, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(dense_counts, ragged_counts)
    pairs, touched = (int(c) for c in ragged_counts)
    assert 0 < touched <= pairs <= tokens * top_k
    if not share:
        assert pairs == tokens * top_k   # every pair lands on a held expert


@pytest.mark.parametrize("tokens, inplace", [(DENSE_MAX_TOKENS, 1),
                                             (DENSE_MAX_TOKENS + 1, 0)])
def test_dropless_layer_reads_in_place_up_to_its_token_bound(tokens, inplace):
    cfg = MoEConfig(d_model=32, d_ff=16, n_experts=64, top_k=6, n_shared=2,
                    norm_topk=False, share=(8, 8))
    p = init_params(jax.random.PRNGKey(0), moe_defs(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, 32),
                          jnp.float32)
    out, counts = moe_apply_dropless(p, cfg, x)
    helper = held_dense if inplace else held_ragged
    want, want_counts = held_part(helper, p, cfg, x)
    np.testing.assert_allclose(out, want + mlp_apply(p["shared"], x),
                               rtol=1e-6, atol=1e-6)
    assert counts.tolist() == want_counts.tolist() + [inplace]


def checksum(leaf):
    """The float32 values' sum and position-weighted sum, in float64."""
    v = np.asarray(jax.device_get(leaf)).astype(np.float64).ravel()
    w = (np.arange(v.size) % 251 + 1).astype(np.float64)
    return [float(v.sum()), float((v * w).sum())]


@pytest.mark.parametrize("key", sorted(PIN["weights"]))
def test_weights_equal_the_pinned_ones(key):
    seed, layout = key.split("/")
    padded = lm.arch("tiny", TINY).padded_vocab if layout == "program" else 0
    w = lm.make_weights(TINY, int(seed), layout, padded)
    got = {jax.tree_util.keystr(p): [list(np.shape(a)), str(a.dtype)]
           + checksum(a)
           for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == PIN["weights"][key]


# DeepSeek-V2-Lite by hand: embedding and head 2 x 102400 x 2048; per layer
# attention 2048 x 3072 + 2048 x 576 + 512 + 512 x 4096 + 2048 x 2048 and two
# norms (13,767,168); layer 0's SwiGLU 3 x 2048 x 10944; each of 26 MoE
# layers a router 2048 x 64, 3 x 2048 x 1408 per held expert and the shared
# 3 x 2048 x 2816; the final norm 2048.
HAND = {8: 3_110_989_312, 64: 15_706_484_224}


@pytest.mark.parametrize("held", sorted(HAND))
def test_param_count_is_the_hand_count(held):
    cfg = lm.load_config("deepseek-v2-lite")
    cfg = {**cfg, "n_routed_experts": held, "held_experts": list(range(held))}
    assert lm.param_count(cfg) == HAND[held]
    assert build_model(lm.arch("x", cfg)).param_count() == HAND[held]


@pytest.mark.parametrize("name, want", [("deepseek-v2-lite", 27 * 576 * 2),
                                        ("qwen1.5-0.5b", 2 * 24 * 1024 * 2)])
def test_tuner_cache_bytes_per_position_come_from_the_model(name, want):
    model = build_model(lm.arch(name, lm.load_config(name)))
    assert stats_from_model(model).kv_bytes_per_pos == want


def test_engine_counts_expert_rows_in_its_one_transfer():
    arch, params = tiny_program()
    engine = ServeEngine(build_model(arch), batch_size=2, max_seq=32,
                         params=params)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(1, 1000, 8).astype(np.int32),
                    max_new_tokens=5) for i in range(2)]
    before = obs.snapshot()
    out = engine.generate(reqs)
    after = obs.snapshot()

    def moved(name):
        return after.get(name, 0) - before.get(name, 0)

    assert all(len(v) == 5 for v in out.values())
    assert moved("engine.host_pulls") == 5          # one a pass, as before
    assert moved("engine.decode_steps") == 4
    # 4 calls of 2 rows, top-4 over 2 MoE layers: at most 64 pairs
    assert 0 < moved("moe.pairs_held") <= 64
    assert 0 < moved("moe.experts_touched") <= min(
        moved("moe.pairs_held"), 4 * 2 * 8)
    # decode calls of 2 tokens read every MoE layer's experts in place
    assert moved("moe.inplace_layers") == 4 * 2
