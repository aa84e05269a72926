"""Find the knee of an open-loop serving cell by a sweep of offered rates.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 4 5 6 7

One set-up, then one window per rate with the cell's traffic at that rate.
Each prints a JSON line: the rate, the 95th-percentile latency, tokens per
second, requests left unanswered at the window's end, and how much later the
last quarter of the requests waited than the first (a backlog that grows).
The knee is the highest rate whose backlog does not grow; the cell's file
then fixes its rate at about four fifths of it.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import copy
import json
import math
import os
import statistics
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import run, traffic  # noqa: E402
from bench.drivers import serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    args.trace = 0
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        ctx = run.Context.from_files(args, json.load(f))
    try:
        ctx.attach_device()
    except run.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    run.compile_cache()
    cell = serve.ServeCell(ctx)
    cell.build()
    cell.setup()
    for rate in args.rates:
        wl = copy.deepcopy(ctx.workload)
        wl["loop"]["rate_per_s"] = rate
        cell.wl = wl
        cell.traffic = traffic.Traffic(wl, cell.m["vocab"], ctx.seconds,
                                       ctx.seed)
        cell.served, cell.ticks = [], []
        end = cell.window(ctx.tracer)
        lat = sorted(((r.done if not math.isnan(r.done) else end) - r.due,
                      r.due) for r in cell.served)
        by_due = [x for x, _ in sorted(lat, key=lambda p: p[1])]
        q = max(1, len(by_due) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "p95_s": serve.p95([x for x, _ in lat]),
            "tokens_per_s": sum(len(r.tokens) for r in cell.served) / end,
            "unanswered": sum(math.isnan(r.done) for r in cell.served),
            "last_over_first_quarter": statistics.mean(by_due[-q:])
            / statistics.mean(by_due[:q]),
            "ticks": len(cell.ticks),
            "mean_tick_rows": statistics.mean(t.n for t in cell.ticks),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
