"""The comparison that decides ``correct``.

Serving: once the window has closed and the program's state is freed, a
sample of the answered requests, drawn from the seed, goes through the plain
reference of the configuration (``bench/references/<reference>.py``), with
weights made anew from the seed in the published layout.  The sample holds
the longest answer of every (mix, configuration) the window served with, then
random others until it holds ``check.tokens`` served tokens.  For every
served token the reference gives the gap by which its logit lies below the
reference's best at that position; the widest gap over the sample is held
against ``check.max_logit_gap``.  Every answered request must also carry
exactly the tokens it asked for, each inside the vocabulary.

``serve_control`` reads the same gap for the control: the reference computed
in int8 (``quant="int8"``) put in the program's place, taking the token it
ranks first at each position of the same prompts and answers.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

import numpy as np

from bench import lm

# the gap charged to an answer holding no token or one outside the vocabulary
OUT_OF_VOCAB = 1e9


def reference(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def sample(served, seed: int, tokens: int) -> list:
    """The longest answer of each (mix, served configuration) group, then
    random others, until the sample holds ``tokens`` served tokens."""
    rng = np.random.default_rng([int(seed), 7])
    groups: Dict[Tuple, list] = {}
    for r in served:
        groups.setdefault((r.mix, r.config_key), []).append(r)
    picked = {max(g, key=lambda r: (len(r.tokens), -r.uid)).uid
              for g in groups.values()}
    order = [served[i] for i in rng.permutation(len(served))]
    out = [r for r in served if r.uid in picked]
    have = sum(len(r.tokens) for r in out)
    for r in order:
        if have >= tokens:
            break
        if r.uid not in picked:
            out.append(r)
            picked.add(r.uid)
            have += len(r.tokens)
    return sorted(out, key=lambda r: r.uid)


def gaps(cfg: dict, w: dict, reqs: list, quant: str = "") -> List[float]:
    """Per request, the widest reference-logit gap of its tokens: the served
    ones, or with ``quant`` the ones the quantised reference ranks first."""
    ref = reference(cfg)
    m = lm.dims(cfg)
    out = []
    for r in reqs:
        p, g = len(r.prompt), np.asarray(r.tokens, np.int64)
        if len(g) == 0 or np.any((g < 0) | (g >= m["vocab"])):
            out.append(OUT_OF_VOCAB)
            continue
        seq = np.concatenate([r.prompt, g[:-1]]).astype(np.int32)
        rows = np.arange(p - 1, p - 1 + len(g))
        ref_logits = ref.logits(m, w, seq, rows)
        chosen = g
        if quant:
            chosen = ref.logits(m, w, seq, rows, quant=quant).argmax(-1)
        best = ref_logits.max(-1)
        out.append(float(np.max(best - ref_logits[np.arange(len(g)),
                                                  chosen])))
    return out


def bad_answers(cfg: dict, answered) -> int:
    vocab = lm.dims(cfg)["vocab"]
    return sum(1 for r in answered
               if len(r.tokens) != r.answer_len
               or any(not 0 <= t < vocab for t in r.tokens))


def serve(ctx, answered) -> Tuple[Dict[str, dict], int]:
    """(numbers compared beside their limits, requests answered wrongly)."""
    spec = ctx.workload["check"]
    bad = bad_answers(ctx.config, answered)
    picked = sample(answered, ctx.seed, spec["tokens"])
    w = lm.make_weights(ctx.config, ctx.seed, "hf")
    widest = max(gaps(ctx.config, w, picked), default=OUT_OF_VOCAB)
    return {
        "bad_answers": {"value": bad, "limit": 0},
        "max_logit_gap": {"value": widest, "limit": spec["max_logit_gap"]},
        "sampled_tokens": {"value": sum(len(r.tokens) for r in picked),
                           "limit": spec["tokens"]},
    }, bad


def passed(checks: Dict[str, dict]) -> bool:
    c = checks
    return (c["bad_answers"]["value"] <= c["bad_answers"]["limit"]
            and c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
            and c["sampled_tokens"]["value"] >= c["sampled_tokens"]["limit"])
