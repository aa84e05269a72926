"""Serving driver: load (or init) a model and run batched generation.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --requests 8 --max-new 16

``--autotune`` serves through the online shape-bucketed tuner instead of a
fixed configuration: requests are bucketed by (prompt length, max-new)
deciles, the dominant bucket's configuration comes from the ``ConfigStore``
(``--store``; zero live trials on a hit) or from a handful of live
warm-started trials on a miss, and freshly tuned configs persist for the
next run.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, SMOKES
from repro.core.hwspec import spec_for_device
from repro.launch.cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine, tune_engine_batch


def run(argv=None):
    """Parse the CLI and serve; return the requests, their generated
    tokens and, with ``--autotune``, the tuner and its tick report."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--tune-batch", action="store_true",
                    help="pick batch size by timed trials through the "
                         "ask-tell tuning API before serving")
    ap.add_argument("--autotune", action="store_true",
                    help="serve through the online shape-bucketed tuner "
                         "(drift-triggered live trials, store-backed reuse)")
    ap.add_argument("--store", default=None,
                    help="ConfigStore JSON path for --autotune (tuned "
                         "configs/models persist across runs; default: "
                         "in-memory)")
    ap.add_argument("--live-trials", type=int, default=8,
                    help="max live trials per drift event for --autotune")
    ap.add_argument("--service", default=None,
                    help="tuning-daemon address (host:port) for --autotune: "
                         "drift retunes route through the shared tuning "
                         "service and fall back in-process when it is "
                         "unreachable (start one with "
                         "python -m repro.launch.daemon)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = (SMOKES if args.smoke else ARCHS)[args.arch]
    model = build_model(arch)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, arch.vocab_size,
                                        size=int(rng.integers(4, 16))),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]

    if args.autotune:
        from repro.serve.autotune import (EngineBackend, OnlineAutotuner,
                                          ShapeBucketer, serve_space,
                                          stats_from_model)
        from repro.tuning.store import ConfigStore

        # tuned configs are filed under the chip they were measured on
        hw = spec_for_device(jax.devices()[0])
        backend = EngineBackend(model, rng=jax.random.PRNGKey(0))
        tuner = OnlineAutotuner(
            backend,
            store=ConfigStore(args.store),
            bucketer=ShapeBucketer(max_prompt=args.max_seq,
                                   max_new=max(1, args.max_new)),
            space=serve_space(max_seqs=tuple(sorted(
                {args.max_seq, args.max_seq // 2, 2 * args.max_seq}))),
            stats=stats_from_model(model),
            max_live_trials=args.live_trials,
            hw=hw,
            service=args.service,
        )
        t0 = time.time()
        out, rep = tuner.serve(reqs)
        dt = time.time() - t0
        n = sum(len(v) for v in out.values())
        if rep is not None:
            how = ("reused stored config" if rep.reused
                   else "tuned via service" if rep.via_service
                   else "tuned live")
            print(f"[serve] bucket={rep.bucket} {how} "
                  f"(trials={rep.live_trials}) -> {rep.config}")
        print(f"[serve] {len(reqs)} requests, {n} tokens in {dt:.1f}s "
              f"({n/max(dt, 1e-9):.1f} tok/s)")
        return {"requests": reqs, "outputs": out, "report": rep,
                "tuner": tuner}

    batch = args.batch
    if args.tune_batch:
        params = model.init(jax.random.PRNGKey(0))  # one copy for all trials
        factory = lambda b: ServeEngine(model, batch_size=b,
                                        max_seq=args.max_seq, params=params)
        batch, best_s, hist = tune_engine_batch(factory, reqs)
        print(f"[serve] tuned batch_size={batch} "
              f"({best_s:.2f}s best of {len(hist)} trials)")
    engine = ServeEngine(model, batch_size=batch, max_seq=args.max_seq,
                         rng=jax.random.PRNGKey(0))
    t0 = time.time()
    out = engine.generate(reqs)
    dt = time.time() - t0
    n = sum(len(v) for v in out.values())
    print(f"[serve] {len(reqs)} requests, {n} tokens in {dt:.1f}s "
          f"({n/dt:.1f} tok/s)")
    return {"requests": reqs, "outputs": out, "report": None, "tuner": None}


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
