"""Read a serving cell's control beside the program, seed by seed.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one run of the cell as the benchmark makes it (set-up, a
window of ``--seconds`` at the cell's own load, the check), then, on the same
sample of answered requests, the widest reference-logit gap of the tokens
the control ranks first: the cell's reference with int8 and with fp8
projections.  Prints one JSON line per seed: the program's widest gap and
each control's.
The limit ``check.max_logit_gap`` of a cell lies between the two.  Not part
of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import check, lm, run  # noqa: E402

QUANTS = ("int8", "fp8")


def read(ctx) -> dict:
    res = run.drive(ctx)
    answered = res["records"]["answered"]
    picked = check.sample(answered, ctx.seed,
                          ctx.workload["check"]["tokens"])
    w = lm.make_weights(ctx.config, ctx.seed, "hf")
    out = {"seed": ctx.seed,
           "program": res["checks"]["max_logit_gap"]["value"],
           "tokens": res["checks"]["sampled_tokens"]["value"]}
    for quant in QUANTS:
        out[quant] = max(check.gaps(ctx.config, w, picked, quant=quant))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run.compile_cache()
    for seed in args.seeds:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        ctx = run.Context.from_files(ns, bench)
        try:
            ctx.attach_device()
        except run.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps(read(ctx)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
