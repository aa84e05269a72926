"""End-to-end training driver with checkpoint/restart fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 50 --batch 8 --seq 256 --smoke --ckpt-dir /tmp/ckpt

``--data-model`` must cover every device present (CPU smoke → one chip →
a 2x2 host), and the run refuses a mesh that would leave chips idle.
Fault tolerance: resumes from the latest complete checkpoint; a per-step
watchdog aborts wedged steps so the supervisor (launch/supervisor.py or any
process manager) can re-exec the job, which then restores and continues —
the standard large-pod failure model.  The data pipeline is step-indexed,
so restarts replay the exact batch sequence.
"""
from __future__ import annotations

import argparse
import threading
import time

import jax

from repro.configs import ARCHS, SMOKES
from repro.data.pipeline import DataConfig, make_batch
from repro.distributed.api import activation_sharding
from repro.distributed.sharding import (batch_shardings, default_rules,
                                        make_act_resolver,
                                        train_state_shardings)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model
from repro.optim.adamw import AdamW, warmup_cosine
from repro.train.train_step import (StepConfig, abstract_train_state,
                                    init_train_state, make_train_step)
from repro.checkpoint.checkpointer import Checkpointer


class StepWatchdog:
    """Aborts the process if a step wedges (straggler/deadlock mitigation).

    On a real pod a wedged collective blocks forever; the watchdog converts
    that into a fast failure so the supervisor restarts from the last
    checkpoint instead of burning pod-hours.
    """

    def __init__(self, timeout_s: float):
        self.timeout_s = timeout_s
        self._timer = None

    def arm(self):
        self.disarm()
        self._timer = threading.Timer(self.timeout_s, self._abort)
        self._timer.daemon = True
        self._timer.start()

    def disarm(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    @staticmethod
    def _abort():
        import os
        print("[watchdog] step exceeded timeout — aborting for restart")
        os._exit(42)


def train(arch, mesh, *, steps: int, batch: int, seq: int, lr: float = 3e-4,
          microbatches: int = 1, remat: str = "nothing_saveable",
          step_timeout: float = 600.0, ckpt_dir=None, ckpt_every: int = 10):
    """Train ``arch`` on ``mesh`` from a seeded init (or the latest
    checkpoint in ``ckpt_dir``); return each step's loss and grad norm.

    Params and Adam moments are created already sharded (one jitted init
    with the state's shardings as its output), so no device ever holds the
    whole state."""
    model = build_model(arch)
    rules = default_rules(multi_pod=False)
    optimizer = AdamW(lr=warmup_cosine(lr, max(steps // 10, 1), steps))
    scfg = StepConfig(remat=remat, microbatches=microbatches, loss_chunks=1)
    step_fn = make_train_step(model, optimizer, scfg)

    dcfg = DataConfig(
        vocab_size=arch.vocab_size, seq_len=seq, global_batch=batch,
        frontend=arch.frontend, frontend_len=arch.frontend_len,
        frontend_dim=arch.frontend_dim,
    )

    resolver = make_act_resolver(mesh, rules)
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    watchdog = StepWatchdog(step_timeout)
    history = []

    with mesh:
        with activation_sharding(resolver):
            state_abs = abstract_train_state(model, optimizer)
            state_sh = train_state_shardings(mesh, rules, model.specs(),
                                             state_abs)
            state = jax.jit(
                lambda key: init_train_state(model, optimizer, key),
                out_shardings=state_sh)(jax.random.PRNGKey(0))
            start = 0
            if ckpt is not None:
                got = ckpt.restore_latest(state, state_sh)
                if got[0] is not None:
                    start, state = got
                    print(f"[train] restored checkpoint at step {start}")

            b_sh = batch_shardings(mesh, rules, {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in make_batch(dcfg, 0).items()})
            jit_step = jax.jit(step_fn, in_shardings=(state_sh, b_sh),
                               out_shardings=(state_sh, None),
                               donate_argnums=(0,))
            t0 = time.time()
            for step in range(start, steps):
                batch_ = jax.device_put(make_batch(dcfg, step), b_sh)
                watchdog.arm()
                state, metrics = jit_step(state, batch_)
                loss = float(metrics["loss"])
                watchdog.disarm()
                gnorm = float(metrics["grad_norm"])
                history.append({"step": step, "loss": loss,
                                "grad_norm": gnorm})
                if step % 5 == 0 or step == steps - 1:
                    dt = time.time() - t0
                    print(f"[train] step {step:5d} loss {loss:.4f} "
                          f"gnorm {gnorm:.3f} ({dt:.1f}s)")
                if ckpt is not None and (step + 1) % ckpt_every == 0:
                    ckpt.save(step + 1, state)
            if ckpt is not None:
                ckpt.save(steps, state)
                ckpt.wait()
            if history:
                print(f"[train] done: final loss {history[-1]['loss']:.4f}")
    return history


def run(argv=None):
    """Parse the CLI, train, and return the per-step history."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="nothing_saveable")
    ap.add_argument("--step-timeout", type=float, default=600.0)
    ap.add_argument("--data-model", type=int, nargs=2, default=(1, 1),
                    help="mesh shape (data, model); must use every device")
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = (SMOKES if args.smoke else ARCHS)[args.arch]
    return train(arch, make_host_mesh(*args.data_model), steps=args.steps,
                 batch=args.batch, seq=args.seq, lr=args.lr,
                 microbatches=args.microbatches, remat=args.remat,
                 step_timeout=args.step_timeout, ckpt_dir=args.ckpt_dir,
                 ckpt_every=args.ckpt_every)


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
