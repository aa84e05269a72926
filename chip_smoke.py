"""Bring-up check: the system's main path on a TPU, through its entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host with four chips (2x2 mesh)

One chip runs four phases in this one process, each printing its result on
a line of its own:

  kernels  every registered Pallas kernel at its default input with its
           ``default_config`` (conv2d in both tap modes), checked against
           the kernel's jnp reference at the kernel tests' tolerances
  tune     a random search over the matmul space at 2048^3 whose trials
           time compiled kernel calls on the chip; a configuration the
           compiler refuses is an ``inf`` trial
  train    ``repro.launch.train`` on full-width qwen1.5-0.5b for 3 steps
  serve    ``repro.launch.serve --autotune`` on full-width qwen1.5-0.5b,
           with live tuning trials timed on the chip

``--chips 4`` runs only the sharded path: the first step of a depth-cut
qwen2.5-3b (published widths) on one chip and on the 2x2 mesh, whose losses
and grad norms must agree, then full-width qwen2.5-3b training on the mesh.

The script exits non-zero without a result line when JAX finds no TPU or
when any phase fails.  Its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

LN_VOCAB_TOL = 1.0      # first loss of a seeded init lies within this of ln V
BF16_RTOL = 2e-2        # 1-chip vs 2x2 agreement of loss and grad norm
KERNEL_TOL = {          # relative max error, as in tests/test_kernels.py
    "matmul": 2e-4, "transpose": 2e-4, "coulomb": 5e-4, "nbody": 1e-3,
    "conv2d": 1e-3, "attention": 2e-3,
}
TUNE_TRIALS = 12
TIMED_CALLS = 5


class PhaseFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def compiled_since(before: dict, after: dict):
    """Backend-compile seconds between two ``obs.snapshot()``s, and the
    three functions that took most of them."""
    secs = {f: s - before["compile.seconds"].get(f, 0.0)
            for f, s in after["compile.seconds"].items()}
    top = sorted(secs.items(), key=lambda kv: -kv[1])[:3]
    return sum(secs.values()), [(f, round(s, 1)) for f, s in top if s > 0]


def rel_err(out, ref):
    import jax.numpy as jnp

    err = float(jnp.max(jnp.abs(out - ref)))
    return err / (float(jnp.max(jnp.abs(ref))) + 1e-9)


def reference(bm, args, kw):
    import jax

    # the plain float32 reference: full-precision matmuls on the chip
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(bm.ref(*args, **kw))


def phase_kernels():
    import jax
    import numpy as np

    from repro.kernels.registry import BENCHMARKS

    rng = np.random.default_rng(0)
    for name, bm in BENCHMARKS.items():
        args = bm.make_args(bm.default_input, rng)
        kw = ({"grid_size": bm.default_input.grid_size}
              if name == "coulomb" else {})
        ref = reference(bm, args, kw)
        cfgs = [bm.default_config]
        if name == "conv2d":
            cfgs.append(dict(bm.default_config, UNROLL_TAPS=0))
        for cfg in cfgs:
            t0 = time.perf_counter()
            out = jax.block_until_ready(bm.run(cfg, *args, **kw))
            secs = time.perf_counter() - t0
            err = rel_err(out, ref)
            print(f"[kernels] {name} {bm.default_input.tag} {cfg}: "
                  f"rel err {err:.3e} (tol {KERNEL_TOL[name]:.0e}), "
                  f"first call {secs:.2f}s", flush=True)
            check(out.shape == ref.shape, f"{name}: shape {out.shape} "
                  f"!= reference {ref.shape}")
            check(err < KERNEL_TOL[name], f"{name}: rel err {err:.3e}")
    print(f"[kernels] PASS: {len(BENCHMARKS)} kernels match their "
          "references", flush=True)


def phase_tune():
    import jax
    import numpy as np

    from repro.core.evaluate import FunctionEvaluator
    from repro.core.searcher import run_search
    from repro.kernels.registry import BENCHMARKS
    from repro.kernels.matmul.space import make_space
    from repro.tuning import SEARCHERS

    bm = BENCHMARKS["matmul"]
    inp = bm.default_input
    space = make_space(inp)
    a, b = bm.make_args(inp, np.random.default_rng(0))

    def trial(cfg):
        t0 = time.perf_counter()
        try:
            call = jax.jit(lambda x, y: bm.run(cfg, x, y)).lower(a, b).compile()
        except Exception as e:  # refused by the compiler: a known-bad config
            first = (str(e).strip().splitlines() or [type(e).__name__])[0]
            print(f"[tune] refused {cfg}: {first[:160]}", flush=True)
            return math.inf
        compile_s = time.perf_counter() - t0
        jax.block_until_ready(call(a, b))                      # warm-up
        times = []
        for _ in range(TIMED_CALLS):
            t0 = time.perf_counter()
            jax.block_until_ready(call(a, b))
            times.append(time.perf_counter() - t0)
        rt = float(np.median(times))
        print(f"[tune] trial {cfg}: {rt * 1e3:.3f} ms median of "
              f"{TIMED_CALLS} (compile {compile_s:.2f}s)", flush=True)
        return rt

    ev = FunctionEvaluator(space, trial)
    run_search(SEARCHERS["random"](space, seed=0), ev, TUNE_TRIALS)
    runtimes = [rt for _, rt in ev.history()]
    finite = [rt for rt in runtimes if math.isfinite(rt)]
    check(finite, "no trial ran")
    best = space[ev.best_index]
    err = rel_err(bm.run(best, a, b), reference(bm, (a, b), {}))
    check(err < KERNEL_TOL["matmul"], f"best config rel err {err:.3e}")
    flops = 2.0 * inp.m * inp.n * inp.k
    print(f"[tune] PASS: {len(runtimes)} trials, {len(finite)} timed, "
          f"{len(runtimes) - len(finite)} refused; best {best} "
          f"{ev.best_runtime * 1e3:.3f} ms ({flops / ev.best_runtime / 1e12:.1f}"
          f" TFLOP/s f32 inputs), rel err {err:.3e}", flush=True)


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_train():
    import jax

    from repro.configs import ARCHS
    from repro.launch import train

    arch = ARCHS["qwen1.5-0.5b"]
    hist = train.run(["--arch", arch.name, "--steps", "3"])
    losses = [h["loss"] for h in hist]
    check(len(hist) == 3, f"{len(hist)} steps ran")
    check(all(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"])
              for h in hist), f"non-finite step: {hist}")
    ln_v = math.log(arch.vocab_size)
    check(abs(losses[0] - ln_v) < LN_VOCAB_TOL,
          f"first loss {losses[0]:.4f} far from ln V = {ln_v:.4f}")
    print(f"[train] PASS: {arch.name} losses {losses} (ln V {ln_v:.4f}); "
          f"peak_bytes_in_use {peak_bytes(jax.devices()[0])}", flush=True)


def phase_serve():
    from repro.launch import serve

    res = serve.run(["--arch", "qwen1.5-0.5b", "--autotune",
                     "--live-trials", "4"])
    out, rep, tuner = res["outputs"], res["report"], res["tuner"]
    for r in res["requests"]:
        got = len(out.get(r.uid, []))
        check(got == r.max_new_tokens,
              f"request {r.uid}: {got} of {r.max_new_tokens} tokens")
    timed = [rt for _, rt in rep.history if math.isfinite(rt)]
    check(rep.live_trials >= 1 and tuner.backend.measure_calls >= 1
          and timed, f"no live trial timed: {rep}")
    print(f"[serve] PASS: {len(out)} requests served, "
          f"{sum(map(len, out.values()))} tokens; {rep.live_trials} live "
          f"trials (timed waves {[round(t, 4) for t in timed]} s), "
          f"chose {rep.config}", flush=True)


def phase_sharded():
    import jax
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs import ARCHS
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    full = ARCHS["qwen2.5-3b"]
    cut = full.scaled(name="qwen2.5-3b-4l", n_layers=4)
    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
               ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    kw = dict(steps=1, batch=8, seq=256)
    h1 = train.train(cut, one, **kw)[0]
    h4 = train.train(cut, make_host_mesh(2, 2), **kw)[0]
    for key in ("loss", "grad_norm"):
        a, b = h1[key], h4[key]
        check(math.isfinite(a) and abs(a - b) <= BF16_RTOL * abs(a),
              f"{key}: 1 chip {a} vs 2x2 {b}")
    print(f"[sharded] 1 chip vs 2x2, {cut.name} step 0: loss {h1['loss']} "
          f"vs {h4['loss']}, grad_norm {h1['grad_norm']} vs "
          f"{h4['grad_norm']} (rtol {BF16_RTOL})", flush=True)

    hist = train.run(["--arch", full.name, "--steps", "3",
                      "--data-model", "2", "2"])
    check(len(hist) == 3 and all(math.isfinite(h["loss"]) for h in hist),
          f"full-width steps: {hist}")
    peaks = [peak_bytes(d) for d in jax.devices()]
    print(f"[sharded] PASS: {full.name} on 2x2 losses "
          f"{[h['loss'] for h in hist]}; peak_bytes_in_use per chip {peaks}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) present", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro import obs
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    first = obs.snapshot()
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)

    phases = ([phase_sharded] if args.chips == 4 else
              [phase_kernels, phase_tune, phase_train, phase_serve])
    for phase in phases:
        t0, before = time.perf_counter(), obs.snapshot()
        try:
            phase()
        except PhaseFailed as e:
            print(f"[{phase.__name__[6:]}] FAIL: {e}", file=sys.stderr)
            return 1
        secs, top = compiled_since(before, obs.snapshot())
        print(f"[{phase.__name__[6:]}] {time.perf_counter() - t0:.1f}s wall, "
              f"{secs:.1f}s backend compile; most: {top}", flush=True)
    last = obs.snapshot()
    secs, _ = compiled_since(first, last)
    hits = last.get("compile.cache_hits", 0) - first.get(
        "compile.cache_hits", 0)
    print(f"[total] {time.perf_counter() - t_start:.1f}s wall, "
          f"{secs:.1f}s backend compile, {hits} persistent cache hits",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
