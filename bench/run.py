"""Run one benchmark cell once, on the chips of the host it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its traffic and driver are in
``bench/workloads/<cell>.json``, its model in ``bench/configs/<config>.json``.
With ``--trace 0`` the result line carries the cell's end-to-end metrics; with
``--trace 1`` a slice of the window runs under the profiler and the line
carries the per-layer metrics (``bench/metrics/<name>.py``), the device's busy
seconds and a breakdown.  The last line of standard output is one JSON
object; the numbers compared for ``correct`` are also the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks for,
or on a ``device_kind`` missing from ``bench/peaks.json``, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# the checkout root, not bench/, is on the path: bench's modules import as
# ``bench.<name>`` and shadow nothing of the standard library
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path[0] = ROOT
else:
    sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

HOST_SPANS = ("tick:", "make_requests", "traced")


class NoChip(Exception):
    pass


class CompileClock:
    """Backend-compile seconds and compilations, from JAX's monitoring
    events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


class Tracer:
    """Profiles the window from its first tick to the first tick start
    ``seconds`` later; a no-op without ``--trace 1``."""

    def __init__(self, enabled: bool, spec: dict):
        self.enabled = enabled
        self.length = float(spec.get("seconds", 5.0))
        self.dir = None
        self.on = self.off = None
        self._span = None

    def arm(self, t0: float) -> None:
        self.t0 = t0

    def poll(self, now: float) -> None:
        import jax

        from bench import trace

        if not self.enabled or self.off is not None:
            return
        if self.on is None:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir, profiler_options=trace.options())
            self._span = jax.profiler.TraceAnnotation("traced")
            self._span.__enter__()
            self.on = time.perf_counter() - self.t0
        elif now >= self.on + self.length:
            self.stop()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        self.off = time.perf_counter() - self.t0
        jax.profiler.stop_trace()

    def finish(self) -> None:
        if self.enabled and self.on is not None and self.off is None:
            self.stop()

    def reduce(self):
        """The trace cut to the ``traced`` span, or None."""
        from bench import trace

        if self.dir is None:
            return None
        try:
            ex = trace.extract(trace.find(self.dir), HOST_SPANS)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        spans = [(s, d) for n, s, d in ex["host"] if n == "traced"]
        if not spans:
            return None
        s, d = spans[0]
        return trace.Trace(ex, s, s + d)


class Context:
    """What a driver and a metric reader may read of this run."""

    def __init__(self, bench: dict, name: str, workload: dict, config: dict,
                 seed: int, seconds: float, trace: bool):
        self.bench = bench
        self.name = name
        self.cell = {c["name"]: c for c in bench["workloads"]}[name]
        self.workload = workload
        self.config_name = self.cell["config"]
        self.config = config
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.chips = int(self.cell["chips"])
        self.setup_s = None
        self.memory_peak = None
        self.tracer = Tracer(self.trace, workload.get("trace", {}))

    @classmethod
    def from_files(cls, args, bench: dict) -> "Context":
        from bench import lm

        cells = {c["name"]: c for c in bench["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"unknown workload {args.workload!r}; "
                             f"known: {sorted(cells)}")
        with open(os.path.join(BENCH, "workloads",
                               f"{args.workload}.json")) as f:
            workload = json.load(f)
        config = lm.load_config(cells[args.workload]["config"])
        return cls(bench, args.workload, workload, config, args.seed,
                   args.seconds, args.trace)

    def attach_device(self):
        """Take the host's chips."""
        import jax

        with open(os.path.join(BENCH, "peaks.json")) as f:
            peaks = json.load(f)
        devices = jax.devices()
        dev = devices[0]
        if dev.platform != "tpu":
            raise NoChip(f"needs a TPU, JAX found {dev.platform}")
        if dev.device_kind not in peaks["kinds"]:
            raise NoChip(f"no peaks for device kind {dev.device_kind!r} in "
                         "bench/peaks.json")
        if len(devices) < self.chips:
            raise NoChip(f"the cell needs {self.chips} chips, JAX found "
                         f"{len(devices)}")
        self.devices = devices[:self.chips]
        self.kind = dev.device_kind
        self.peaks = peaks["kinds"][self.kind]
        self.clock = CompileClock()

    def end_setup(self):
        self.setup_s = time.perf_counter() - T_START
        self.compile_at_window = (self.clock.seconds, self.clock.count)
        self.sampler = MemorySampler(self.devices)

    def read_memory(self):
        """At the window's close: its compiles and the most device memory
        in use while it ran."""
        self.compile_in_window = (
            self.clock.seconds - self.compile_at_window[0],
            self.clock.count - self.compile_at_window[1])
        self.memory_peak = self.sampler.stop()


class MemorySampler:
    """The most bytes in use on the fullest chip, read every ``period``
    seconds on a thread of its own from the window's start to ``stop``.
    The process's own peak is no use here: set-up reaches it."""

    def __init__(self, devices, period: float = 0.02):
        import threading

        self.devices = devices
        self.period = period
        self.most = 0
        self._halt = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _read(self) -> None:
        self.most = max([self.most] + [
            (d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in self.devices])

    def _loop(self) -> None:
        while not self._halt.wait(self.period):
            self._read()

    def stop(self) -> int:
        self._halt.set()
        self._thread.join()
        self._read()
        return self.most


def compile_cache():
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache`` in the checkout; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def read_metric(name: str, ctx, res):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx, res)


def listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def breakdown(tr) -> dict:
    ops = sorted(tr.op_time().items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in tr.idle_gaps()[:10]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ctx = Context.from_files(args, bench)
    try:
        ctx.attach_device()
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    compile_cache()
    return report(ctx, drive(ctx))


def drive(ctx) -> dict:
    driver = importlib.import_module(
        f"bench.drivers.{ctx.workload['driver']}")
    return driver.run(ctx)


def result(ctx, res) -> dict:
    """The result line's object."""
    from bench import check

    tr = ctx.tracer.reduce() if ctx.trace else None
    res["trace"] = tr
    metrics = {}
    kind = "per_layer" if ctx.trace else "end_to_end"
    for m in ctx.bench[kind]:
        if not listed(m, ctx.name):
            continue
        if kind == "end_to_end":
            value = ctx.setup_s if m["name"] == "setup_s" \
                else res["end_to_end"].get(m["name"])
        else:
            value = read_metric(m["name"], ctx, res)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": ctx.memory_peak}
    out = {"correct": check.passed(res["checks"]),
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_ns() * 1e-9
        device["window_s"] = tr.window_ns * 1e-9
        out["breakdown"] = breakdown(tr)
    out["checks"] = res["checks"]
    return out


def report(ctx, res) -> int:
    out = result(ctx, res)
    print(f"bench: set-up {ctx.setup_s:.3f} s; in the window "
          f"{ctx.compile_in_window[1]} compiles, "
          f"{ctx.compile_in_window[0]:.3f} s", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
