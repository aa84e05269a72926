"""§Perf cell C: the paper's profile-based searcher autotunes the
DISTRIBUTED STEP CONFIG of qwen2.5-3b train_4k on the production mesh,
through the public ``repro.tuning`` API.

Training phase: ``TuningSession.train_on_evaluator`` compiles a deliberate
sample of the step space and fits the TP -> PC_ops model.  Autotuning:
profile -> bottleneck -> ΔPC -> biased step, against REAL compiles, driven
ask-tell.  Compared with random search at the same budget.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/autotune_train_step.py \
        [--arch qwen2.5-3b] [--budget 10] [--out step_tune.json]
"""
import argparse
import json
import time

from repro.core.step_tuner import CompiledStepEvaluator
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import use_host_devices
from repro.tuning import TuningSession


def main():
    use_host_devices(512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--budget", type=int, default=10)
    ap.add_argument("--train-samples", type=int, default=14)
    ap.add_argument("--out", default="step_tune.json")
    ap.add_argument("--save-model", default=None,
                    help="also write the trained TP->PC model JSON artifact")
    args = ap.parse_args()
    enable_compile_cache()

    t0 = time.time()
    ev_train = CompiledStepEvaluator(args.arch, args.shape)
    space = ev_train.space
    print(f"step space: {len(space)} configs")

    # --- training phase: deliberate sample -> TP->PC model ---------------
    session = TuningSession(space, seed=0)
    print(f"training phase: compiling <= {args.train_samples} sampled configs")
    session.train_on_evaluator(ev_train, values_per_param=2,
                               max_samples=args.train_samples)
    print(f"model trained ({ev_train.compile_seconds:.0f}s of compiles, "
          f"{ev_train.steps} empirical tests)")
    if args.save_model:
        session.save_model(args.save_model)
        print(f"model artifact -> {args.save_model}")

    # --- autotuning: profile-based vs random at the same budget ----------
    results = {"space": len(space), "train_samples": ev_train.steps,
               "budget": args.budget}
    for label in ("profile", "random"):
        ev = CompiledStepEvaluator(args.arch, args.shape)
        ev._cache.update(ev_train._cache)  # share compile cache across
        extra = {"n": 3} if label == "profile" else {}
        session.tune(budget=args.budget, searcher=label, evaluator=ev,
                     seed=1, **extra)
        best = space[ev.best_index]
        print(f"[{label}] best {ev.best_runtime*1e3:.1f}ms after "
              f"{ev.steps} tests: {best}")
        results[label] = {"best_ms": ev.best_runtime * 1e3,
                          "best_config": best, "steps": ev.steps}
    results["train_best_ms"] = ev_train.best_runtime * 1e3
    results["train_best_config"] = space[ev_train.best_index]
    results["total_seconds"] = time.time() - t0
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"done in {time.time()-t0:.0f}s -> {args.out}")


if __name__ == "__main__":
    main()
