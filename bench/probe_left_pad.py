"""Witness of the serve engine's left padding, against the plain reference.

    python bench/probe_left_pad.py --config qwen1.5-0.5b --seeds 1 2 3

For each seed, prompts of 128, 512, 1024 and 1536 tokens (the range of
log-normal chat prompts of median 512, clipped), each with a greedy
answer of 32 tokens, served by the program's ``ServeEngine`` (4 slots,
``MAX_SEQ`` 2048) twice: all four as one wave, which the engine left-pads to
the longest prompt, and each alone, which pads nothing.  Prints one JSON
line per seed: each row's widest reference-logit gap (``check.gaps``) both
ways.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import check, lm, run  # noqa: E402

LENGTHS = (128, 512, 1024, 1536)
ANSWER = 32
MAX_SEQ = 2048


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("probe: needs a TPU", file=sys.stderr)
        return 2
    run.compile_cache()
    from repro.models.registry import build_model
    from repro.serve.engine import Request, ServeEngine

    cfg = lm.load_config(args.config)
    arch = lm.arch(args.config, cfg)
    model = build_model(arch)
    vocab = lm.dims(cfg)["vocab"]
    for seed in args.seeds:
        params = lm.make_weights(cfg, seed, "program", arch.padded_vocab)
        engine = ServeEngine(model, batch_size=len(LENGTHS), max_seq=MAX_SEQ,
                             params=params)
        rng = np.random.default_rng(seed)
        reqs = [Request(uid=i, prompt=rng.integers(1, vocab, size=n,
                                                   dtype=np.int32),
                        max_new_tokens=ANSWER)
                for i, n in enumerate(LENGTHS)]
        wave = engine.generate(reqs)
        alone = {}
        for r in reqs:
            alone.update(engine.generate([r]))
        del engine, params
        w = lm.make_weights(cfg, seed, "hf")
        out = {"seed": seed, "prompt_len": list(LENGTHS)}
        for name, got in (("one_wave", wave), ("alone", alone)):
            rows = [types.SimpleNamespace(prompt=r.prompt, tokens=got[r.uid])
                    for r in reqs]
            out[name] = check.gaps(cfg, w, rows)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
