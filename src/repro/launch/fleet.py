"""Fleet tuning CLI: many (kernel × input × hardware) jobs, one pool.

Builds ``TuningJob``s from the kernel registry for every requested
(kernel, hardware) pair, runs them through a ``FleetTuner`` over the
chosen worker backend, and persists tuned configs + portable model
artifacts into a shared ``ConfigStore`` — so re-running with more hardware
(or more shapes) warm-starts from what the fleet already learned.

    PYTHONPATH=src python -m repro.launch.fleet \
        --kernels matmul,transpose --hw tpu_v4,tpu_v5e \
        --store fleet_store.json --workers 4 --budget 25

    # subprocess lanes, one evaluation process each
    PYTHONPATH=src python -m repro.launch.fleet --backend subprocess \
        --workers 2 --kernels matmul --hw tpu_v5e

    # whole-system mode: kernel tiles + train-step sharding + serve
    # geometry for one model-zoo entry, one fleet, one store
    PYTHONPATH=src python -m repro.launch.fleet --system qwen2.5-3b \
        --hw tpu_v5e --store system_store.json

    # or cherry-pick registered problems by kind:name spec
    PYTHONPATH=src python -m repro.launch.fleet \
        --problem sharding:qwen2.5-3b/train_4k --problem serve:p9n9
"""
from __future__ import annotations

import argparse
import json
import time


def build_pool(backend: str, workers: int):
    from repro.fleet import (SubprocessWorkerPool, ThreadWorkerPool,
                             VirtualWorkerPool)

    if backend == "virtual":
        return VirtualWorkerPool(workers=workers)
    if backend == "thread":
        return ThreadWorkerPool(workers=workers)
    if backend == "subprocess":
        return SubprocessWorkerPool(workers=workers)
    raise ValueError(f"unknown backend {backend!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--kernels", default="matmul,transpose",
                    help="comma-separated registry kernel names")
    ap.add_argument("--inputs", default=None,
                    help="comma-separated input keys, one per kernel "
                    "(default: each kernel's default input)")
    ap.add_argument("--problem", action="append", default=None,
                    help="tune registered problems 'kind:name' instead of "
                    "--kernels (repeatable / comma-separated), e.g. "
                    "kernel:matmul/128, sharding:qwen2.5-3b/train_4k, "
                    "serve:p9n9")
    ap.add_argument("--system", default=None,
                    help="whole-system mode: one invocation tunes kernel "
                    "tiles + train-step sharding + serve geometry for this "
                    "model-zoo entry through one fleet and one store "
                    "(overrides --kernels/--problem)")
    ap.add_argument("--hw", default="tpu_v4,tpu_v5e",
                    help="comma-separated hardware names (naming drift ok: "
                    "TPUv4 == tpu_v4)")
    ap.add_argument("--backend", default="virtual",
                    choices=("virtual", "thread", "subprocess"))
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--in-flight", type=int, default=None,
                    help="outstanding tests pool-wide (default: --workers)")
    ap.add_argument("--in-flight-max", type=int, default=None,
                    help="make in_flight ELASTIC between [--in-flight, "
                    "this]: the driver grows/shrinks outstanding work from "
                    "pool backpressure (live lanes, measurement variance)")
    ap.add_argument("--retries", type=int, default=2,
                    help="max resubmissions per failed test on another "
                    "lane (default: 2)")
    ap.add_argument("--known-bad-after", type=int, default=2,
                    help="mark a config known-bad after this many "
                    "failures of its own measurement (default: 2)")
    ap.add_argument("--straggler-factor", type=float, default=None,
                    help="time out tests outstanding longer than this "
                    "factor times the job's rolling cost estimate and "
                    "resubmit them elsewhere (default: disabled)")
    ap.add_argument("--park-factor", type=float, default=None,
                    help="park model-backed jobs whose measured best is "
                    "already within this factor of their predicted best "
                    "runtime (default: disabled)")
    ap.add_argument("--budget", type=int, default=25,
                    help="empirical-test budget per job")
    ap.add_argument("--searcher", default=None,
                    help="force one searcher for every job (default: "
                    "warm_start on store hit, random cold)")
    ap.add_argument("--store", default=None,
                    help="shared ConfigStore path (default: in-memory)")
    ap.add_argument("--no-publish", action="store_true",
                    help="do not train/publish missing model artifacts")
    ap.add_argument("--transfer", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="when every exact-space stored model misses a "
                    "job, warm-start it from the most structurally "
                    "similar same-kind space's model (--no-transfer pins "
                    "the legacy exact-space ladder)")
    ap.add_argument("--transfer-threshold", type=float, default=None,
                    help="minimum structural similarity (counter Jaccard "
                    "x parameter overlap, in [0,1]) a cross-space model "
                    "must clear to be used (default: the library's "
                    "conservative threshold)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write a JSON report here")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    from repro.fleet import (FleetTuner, job_from_problem,
                             job_from_registry)
    from repro.kernels.registry import BENCHMARKS
    from repro.tuning import ConfigStore

    hws = [h.strip() for h in args.hw.split(",") if h.strip()]
    if args.system is not None:
        from repro.tuning.problem import system_problems
        try:
            problems = system_problems(args.system)
        except KeyError as exc:
            raise SystemExit(f"--system: {exc}")
        jobs = [job_from_problem(p, hw, budget=args.budget,
                                 seed=args.seed, searcher=args.searcher)
                for p in problems for hw in hws]
    elif args.problem:
        from repro.tuning.problem import parse_problem
        specs = [s.strip() for chunk in args.problem
                 for s in chunk.split(",") if s.strip()]
        problems = []
        for spec in specs:
            try:
                problems.append(parse_problem(spec))
            except (KeyError, ValueError) as exc:
                raise SystemExit(f"--problem {spec!r}: {exc}")
        jobs = [job_from_problem(p, hw, budget=args.budget,
                                 seed=args.seed, searcher=args.searcher)
                for p in problems for hw in hws]
    else:
        kernels = [k.strip() for k in args.kernels.split(",") if k.strip()]
        if args.inputs is not None:
            inputs = [i.strip() for i in args.inputs.split(",")]
            if len(inputs) != len(kernels):
                raise SystemExit(
                    "--inputs must list one key per --kernels entry")
        else:
            inputs = []
            for k in kernels:
                bm = BENCHMARKS[k]
                inputs.append(next(key for key, v in bm.inputs.items()
                                   if v is bm.default_input))
        jobs = [job_from_registry(k, inp, hw, budget=args.budget,
                                  seed=args.seed, searcher=args.searcher)
                for k, inp in zip(kernels, inputs) for hw in hws]
    store = ConfigStore(args.store)
    pool = build_pool(args.backend, args.workers)
    t0 = time.time()
    tuner = FleetTuner(jobs, pool, store=store,
                       in_flight=args.in_flight,
                       in_flight_max=args.in_flight_max,
                       retries=args.retries,
                       known_bad_after=args.known_bad_after,
                       straggler_factor=args.straggler_factor,
                       park_factor=args.park_factor,
                       publish_models=not args.no_publish,
                       transfer=args.transfer,
                       transfer_threshold=args.transfer_threshold,
                       verbose=args.verbose)
    # SIGINT/SIGTERM drain: stop filling, collect what is in flight,
    # publish/report the completed jobs (same contract as the daemon)
    from repro.launch.signals import install_drain_handlers

    draining = install_drain_handlers(tuner.stop)
    try:
        tuner.begin()
        while tuner.step(max_wait=0.5):
            pass
        report = tuner.finish()
    finally:
        pool.close()
    wall = time.time() - t0

    print(f"[fleet] {len(jobs)} jobs on {args.backend} backend "
          f"({pool.workers} workers, in_flight={report.in_flight})"
          + ("  [DRAINED EARLY]" if draining() else ""))
    for r in sorted(report.results, key=lambda r: r.job):
        mark = " [cancelled]" if r.cancelled else ""
        if r.transfer_from is not None:
            mark += (f" [transfer {r.transfer_from} "
                     f"~{r.transfer_similarity:.2f}]")
        print(f"  {r.job:40s} {'warm' if r.warm_started else 'cold':4s} "
              f"{r.trials:3d} trials  best {r.best_runtime*1e3:9.3f}ms  "
              f"{r.best_config}{mark}")
    print(f"[fleet] pool clock {report.elapsed:.3f}s for "
          f"{report.busy:.3f} worker-seconds of measurement "
          f"(x{report.busy / max(report.elapsed, 1e-12):.2f} concurrency); "
          f"host wall {wall:.1f}s")
    if report.failures or report.timeouts or report.parked:
        print(f"[fleet] faults: {report.failures} failed attempts "
              f"({report.known_bad} known-bad configs), "
              f"{report.timeouts} stragglers timed out, "
              f"{report.abandoned:.3f}s abandoned work charged to busy, "
              f"{report.parked} jobs parked, max retries used "
              f"{report.max_retries_used}")
    if args.store:
        print(f"[fleet] store -> {args.store} ({len(store)} entries)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({
                "backend": args.backend, "workers": pool.workers,
                "in_flight": report.in_flight,
                "pool_elapsed_s": report.elapsed, "busy_s": report.busy,
                "host_wall_s": wall,
                "failures": report.failures,
                "timeouts": report.timeouts,
                "known_bad": report.known_bad,
                "abandoned_s": report.abandoned,
                "parked": report.parked,
                "drained": draining(),
                "jobs": [{
                    "job": r.job, "bucket": r.bucket, "hardware": r.hardware,
                    "searcher": r.searcher, "warm_started": r.warm_started,
                    "trials": r.trials, "best_runtime_s": r.best_runtime,
                    "best_config": r.best_config,
                    "failures": r.failures, "known_bad": r.known_bad,
                    "parked": r.parked, "cancelled": r.cancelled,
                    "transfer_from": r.transfer_from,
                    "transfer_similarity": r.transfer_similarity,
                } for r in report.results],
            }, f, indent=2)
        print(f"[fleet] -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
