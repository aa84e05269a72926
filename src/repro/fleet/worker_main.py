"""Fleet worker subprocess: a JSON-lines evaluation server.

One of these runs per ``SubprocessWorkerPool`` lane.  Requests name a
registered kernel workload (``{"kernel", "input", "hw", "index", "uid",
"profile"}``); the worker rebuilds the workload model from the registry,
prices it through the cost model on the named hardware, and replies with
``{"uid", "runtime", "cost"}`` (plus ``ops``/``stress`` when profiled).

The worker never imports jax: several lanes run side by side, and a chip
belongs to one process at a time.

Protocol extras: ``{"op": "ping"}`` → ``{"op": "pong"}``
(startup handshake), ``{"op": "shutdown"}`` or EOF → exit.  Errors are
reported per-request (``{"uid", "error", ...}``), never by crashing the
worker.  ``attempt`` is echoed back verbatim so the pool can correlate
retries.

Fault-injection hooks (tests/benchmarks for the fleet's failure policies):
a payload with ``"sim_fail": true`` replies with an injected error instead
of evaluating; ``"sim_crash": true`` makes the worker process exit
immediately WITHOUT replying — the deterministic stand-in for a lane dying
with a test in flight (the pool's reader sees EOF and fails the item as
kind ``"lane"``).
"""
from __future__ import annotations

import json
import sys
import time


def main() -> int:
    from repro.core import costmodel, hwspec
    from repro.kernels.registry import BENCHMARKS

    spaces = {}     # kernel -> TuningSpace (configs resolved by index)

    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        req = json.loads(line)
        op = req.get("op")
        if op == "shutdown":
            break
        if op == "ping":
            print(json.dumps({"op": "pong"}), flush=True)
            continue
        out = {"uid": req.get("uid"), "attempt": int(req.get("attempt", 0))}
        if req.get("sim_crash"):
            # simulate a lane dying mid-test: no reply, immediate exit
            sys.exit(1)
        if req.get("sim_fail"):
            out["error"] = "InjectedFailure: sim_fail requested"
            print(json.dumps(out), flush=True)
            continue
        try:
            bm = BENCHMARKS[req["kernel"]]
            if req["kernel"] not in spaces:
                spaces[req["kernel"]] = bm.make_space()
            space = spaces[req["kernel"]]
            cfg = space[int(req["index"])]
            inp = bm.inputs[req["input"]]
            if "hw_spec" in req:        # unregistered hardware: by numbers
                hw = hwspec.HardwareSpec(**req["hw_spec"])
            else:
                hw = hwspec.get(req["hw"])
            t0 = time.perf_counter()
            cs = costmodel.execute(bm.workload_fn(cfg, inp), hw)
            out["runtime"] = float(cs.runtime)
            out["cost"] = time.perf_counter() - t0
            if req.get("profile"):
                out["ops"] = {k: float(v) for k, v in cs.ops.items()}
                out["stress"] = {k: float(v) for k, v in cs.stress.items()}
        except Exception as e:      # report per-request, keep serving
            out["error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
