"""Batched serving engine: prefill + decode with continuous slot reuse.

The engine owns a fixed-size batch of decode slots.  Requests are admitted
into free slots (their prompt prefilled into the slot's cache region),
decoded greedily until EOS/max-len, then the slot is recycled — a
continuous-batching loop in the vLLM style, expressed over the functional
prefill/decode of the model zoo.

For simplicity slots share one right-aligned cache (prefill fills positions
[0, prompt_len); decode appends) and admission happens between decode
steps.  This is the serving analog of the train driver and the substrate
for the decode dry-run cells.

A pass of the decode loop makes one device-to-host transfer: the (n, 1)
vector of tokens the jitted decode picked.  Where the next decode is needed
whatever those tokens say, it is dispatched before the read, so the device
decodes while the host waits for and handles the pass.  Counters
(``repro.obs``): ``engine.decode_steps``, one a decode dispatched;
``engine.host_pulls``, one a pass (its transfer); ``engine.decode_ahead``,
one a decode dispatched before its pass's read.  The decode of a model with
experts also counts, summed over its MoE layers, the routed (token, expert)
pairs of the experts held here, the held experts that received one and the
layers that read their held experts in place; the three numbers ride in the
pass's one transfer behind the tokens and move ``moe.pairs_held``,
``moe.experts_touched`` and ``moe.inplace_layers``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.registry import Model


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # (L,) int32
    max_new_tokens: int = 16
    eos_id: int = -1            # -1: never stops early
    generated: Optional[List[int]] = None


class ServeEngine:
    def __init__(self, model: Model, batch_size: int, max_seq: int,
                 params=None, rng=None):
        self.model = model
        self.batch = batch_size
        self.max_seq = max_seq
        self.params = params if params is not None else model.init(
            rng if rng is not None else jax.random.PRNGKey(0))

        # the greedy pick runs inside the program (still ``jit_decode``), so a
        # pass hands the host one (n, 1) vector that also feeds the next call
        self._counted = getattr(model, "counts_experts", False)

        def decode(params, cache, batch):
            if not self._counted:
                logits, cache = model.decode(params, cache, batch)
                return (jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32),
                        cache)
            logits, cache, counts = model.decode(params, cache, batch,
                                                 moe_counts=True)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            return (tok, jnp.concatenate([tok[:, 0], counts])), cache

        self._decode = jax.jit(decode, donate_argnums=(1,))

    def generate(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Run all requests to completion, batch_size at a time."""
        out: Dict[int, List[int]] = {}
        queue = list(requests)
        while queue:
            wave = queue[:self.batch]
            queue = queue[self.batch:]
            out.update(self._run_wave(wave))
        return out

    def _run_wave(self, wave: List[Request]) -> Dict[int, List[int]]:
        # A partial wave (the queue tail) is masked to its true size: padding
        # it to self.batch would prefill+decode ghost slots for the full step
        # count — pure wasted compute that also skews wave timings.
        n = len(wave)
        plen = max(len(r.prompt) for r in wave)
        with obs.span("engine.wave", uids=[r.uid for r in wave]):
            with obs.span("engine.prefill"):
                toks = np.zeros((n, plen), np.int32)
                for i, r in enumerate(wave):
                    toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
                batch = {"tokens": jnp.asarray(toks)}
                logits, cache = self.model.prefill(self.params, batch,
                                                   max_seq=self.max_seq)
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                pull = tok
            steps = max(r.max_new_tokens for r in wave)
            done = np.zeros(n, bool)
            gen: List[List[int]] = [[] for _ in range(n)]
            # an exhausted budget means no generated tokens at all — enforce
            # the limit before the first append, not after it
            for i, r in enumerate(wave):
                if r.max_new_tokens <= 0:
                    done[i] = True
            for _ in range(steps):
                with obs.span("engine.step"):
                    # a live row that cannot stop on EOS and still has budget
                    # after this pass needs the next decode whatever the
                    # tokens say: dispatch it before the read, so the device
                    # runs it while the host reads and handles this pass
                    ahead = any(not done[i] and r.eos_id < 0
                                and len(gen[i]) + 1 < r.max_new_tokens
                                for i, r in enumerate(wave))
                    if ahead:
                        nxt = self._step(cache, tok)
                    # moved every pass, by 0 or 1, so the counter exists
                    # wherever this loop ran
                    obs.add("engine.decode_ahead", int(ahead))
                    obs.add("engine.host_pulls")
                    with obs.span("engine.pull"):
                        got = np.ravel(jax.device_get(pull))
                    picked = got[:n].tolist()
                    if got.size > n:
                        obs.add("moe.pairs_held", int(got[n]))
                        obs.add("moe.experts_touched", int(got[n + 1]))
                        obs.add("moe.inplace_layers", int(got[n + 2]))
                    for i, r in enumerate(wave):
                        if not done[i]:
                            gen[i].append(picked[i])
                            if (picked[i] == r.eos_id
                                    or len(gen[i]) >= r.max_new_tokens):
                                done[i] = True
                    if ahead:
                        tok, pull, cache = nxt
                    elif done.all():
                        break
                    else:
                        tok, pull, cache = self._step(cache, tok)
        return {r.uid: gen[i] for i, r in enumerate(wave)}

    def _step(self, cache, tok):
        """Dispatch one decode of the wave's last tokens: (the next tokens,
        what the pass reads of them, the cache)."""
        out, cache = self._decode(self.params, cache, {"tokens": tok})
        obs.add("engine.decode_steps")
        tok, pull = out if self._counted else (out, out)
        return tok, pull, cache

    def warmup(self, prompt_len: int = 4, wave_size: Optional[int] = None
               ) -> None:
        """Run one untimed dummy wave so prefill and at least one decode step
        are compiled before any timed serving/tuning measurement."""
        n = min(wave_size if wave_size is not None else self.batch,
                self.batch)
        plen = max(1, min(prompt_len, self.max_seq - 2))
        reqs = [Request(uid=-1 - i, prompt=np.ones(plen, np.int32),
                        max_new_tokens=2) for i in range(n)]
        self.generate(reqs)

    def warmup_for(self, n_requests: int, prompt_len: int = 4) -> None:
        """Warm every wave size ``generate(n_requests requests)`` will run:
        the full-batch wave and the masked partial tail (distinct jitted
        decode shapes) — so a timed run over ``n_requests`` compiles
        nothing."""
        n = max(1, int(n_requests))
        sizes = {min(self.batch, n)}
        if n % self.batch:
            sizes.add(n % self.batch)
        for size in sorted(sizes):
            self.warmup(prompt_len=prompt_len, wave_size=size)


def tune_engine_batch(
    engine_factory,
    requests: List[Request],
    batch_sizes: Tuple[int, ...] = (1, 2, 4, 8),
    budget: Optional[int] = None,
    seed: int = 0,
    warmup: bool = True,
):
    """Pick the engine batch size by timed end-to-end trials, driven through
    the shared ask-tell tuning API (``FunctionEvaluator`` + registry
    searcher — no counters exist for a serving loop, so the search is
    runtime-only).

    Engines are built once per batch size and reused across repeated trials,
    and each engine serves one untimed warmup wave before its first timed
    trial — otherwise the timed region includes first-call JIT compilation
    of prefill/decode, which scales with batch size and biases selection.

    ``engine_factory(batch_size) -> ServeEngine``.  Returns
    (best_batch_size, best_seconds, history) where history is the public
    per-trial (config index, seconds) trace.
    """
    import time as _time

    from repro.core.evaluate import FunctionEvaluator
    from repro.core.searcher import make_searcher, run_search
    from repro.core.tuning_space import TuningParameter, TuningSpace

    space = TuningSpace([TuningParameter("BATCH", tuple(batch_sizes))],
                        name="serve_batch")
    engines: Dict[int, ServeEngine] = {}

    def _engine(b: int) -> ServeEngine:
        if b not in engines:
            eng = engines[b] = engine_factory(b)
            # warm every wave shape the timed run will hit (full + tail)
            if warmup and hasattr(eng, "warmup_for"):
                eng.warmup_for(len(requests))
            elif warmup and hasattr(eng, "warmup"):
                eng.warmup()
        return engines[b]

    def timed_run(cfg) -> float:
        engine = _engine(int(cfg["BATCH"]))
        t0 = _time.perf_counter()
        engine.generate([dataclasses.replace(r, generated=None)
                         for r in requests])
        return _time.perf_counter() - t0

    ev = FunctionEvaluator(space, timed_run)
    run_search(make_searcher("random", space, seed=seed), ev,
               budget if budget is not None else len(space))
    best = space[ev.best_index]
    return int(best["BATCH"]), ev.best_runtime, ev.history()
