"""Which parameters the program's train step moves, with bf16 and f32 weights.

    python bench/probe_train_update.py --config qwen1.5-0.5b --seeds 1 2 3 \
        --batch 1 --seq 2048 --steps 3 --lr 3e-4

For each seed and each parameter dtype, the weights of the configuration are
made from the seed (the f32 run starts from the same bf16 numbers), the
program's jitted step (``make_train_step`` with ``AdamW`` at a constant
learning rate, ``make_batch`` documents) runs ``--steps`` times, and one JSON
line per run gives, for each leaf of the parameter tree, the share of its
elements that changed and the norm of its change.  ``--config`` is a name
under ``bench/configs`` or a path to a configuration file.  Not part of a
benchmark run: it backs the finding in ``PERF.md`` that keeps the training
cells out of the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, os.path.join(sys.path[0], "src"))

from bench import lm, run  # noqa: E402


def load(config: str) -> dict:
    if os.path.exists(config):
        with open(config) as f:
            return json.load(f)
    return lm.load_config(config)


def probe(cfg: dict, seed: int, dtype: str, args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.pipeline import DataConfig, make_batch
    from repro.models.registry import build_model
    from repro.optim.adamw import AdamW, constant_lr
    from repro.train.train_step import (StepConfig, init_train_state,
                                        make_train_step)

    arch = lm.arch("probe", cfg).scaled(dtype=dtype)
    model = build_model(arch)
    p0 = lm.make_weights(cfg, seed, "program", arch.padded_vocab)
    p0 = jax.tree.map(lambda a: a.astype(jnp.dtype(dtype)), p0)
    optimizer = AdamW(lr=constant_lr(args.lr))
    state = init_train_state(model, optimizer, jax.random.PRNGKey(0))
    state = state._replace(params=jax.tree.map(jnp.copy, p0))
    step = jax.jit(make_train_step(model, optimizer, StepConfig()),
                   donate_argnums=(0,))
    data = DataConfig(vocab_size=arch.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=seed)
    losses = []
    for k in range(args.steps):
        state, metrics = step(state, make_batch(data, k))
        losses.append(float(metrics["loss"]))
    leaves = {}
    for path, a in jax.tree_util.tree_leaves_with_path(state.params):
        b = p0
        for key in path:
            b = b[key.key]
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        leaves[jax.tree_util.keystr(path)] = [
            float(np.mean(d != 0)), float(np.linalg.norm(d))]
    return {"seed": seed, "dtype": dtype, "losses": losses, "leaves": leaves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)
    run.compile_cache()
    cfg = load(args.config)
    for seed in args.seeds:
        for dtype in ("bfloat16", "float32"):
            print(json.dumps(probe(cfg, seed, dtype, args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
