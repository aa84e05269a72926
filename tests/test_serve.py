"""Serving engine: batched generate, slot waves, determinism, and the
partial-wave / token-budget / tuning-timing regression tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import SMOKES
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine, tune_engine_batch


class EchoModel:
    """Deterministic fake model: next token = (last token + 1) % VOCAB.

    jit-compatible prefill/decode with the registry ``Model`` calling
    convention, so engine behavior (wave masking, budgets, EOS) is testable
    exactly, without weights or a real forward pass.
    """

    VOCAB = 32

    def init(self, rng):
        return {"w": jnp.zeros((1,))}

    def _logits(self, tok):
        nxt = (tok + 1) % self.VOCAB
        return jax.nn.one_hot(nxt, self.VOCAB, dtype=jnp.float32)[:, None, :]

    def prefill(self, params, batch, max_seq):
        last = batch["tokens"][:, -1].astype(jnp.int32)
        return self._logits(last), (last + 1) % self.VOCAB

    def decode(self, params, cache, batch):
        tok = batch["tokens"][:, 0].astype(jnp.int32)
        return self._logits(tok), (tok + 1) % self.VOCAB


def echo_engine(batch_size, max_seq=32):
    return ServeEngine(EchoModel(), batch_size=batch_size, max_seq=max_seq,
                       rng=jax.random.PRNGKey(0))


def _count_decodes(engine):
    """Wrap ``engine._decode`` to record decode-call token shapes."""
    calls = []
    orig = engine._decode

    def counting(params, cache, batch):
        calls.append(tuple(batch["tokens"].shape))
        return orig(params, cache, batch)

    engine._decode = counting
    return calls


@pytest.fixture(scope="module")
def engine():
    model = build_model(SMOKES["qwen1.5-0.5b"])
    return ServeEngine(model, batch_size=2, max_seq=32,
                       rng=jax.random.PRNGKey(7))


def _reqs(n, rng):
    return [
        Request(uid=i,
                prompt=rng.integers(1, 500, size=rng.integers(3, 8)),
                max_new_tokens=5)
        for i in range(n)
    ]


def test_generate_batch(engine):
    rng = np.random.default_rng(0)
    out = engine.generate(_reqs(2, rng))
    assert set(out) == {0, 1}
    for toks in out.values():
        assert len(toks) == 5
        assert all(0 <= t < 512 for t in toks)


def test_generate_more_requests_than_slots(engine):
    rng = np.random.default_rng(1)
    out = engine.generate(_reqs(5, rng))
    assert set(out) == set(range(5))


def test_generate_deterministic(engine):
    rng1 = np.random.default_rng(2)
    rng2 = np.random.default_rng(2)
    a = engine.generate(_reqs(2, rng1))
    b = engine.generate(_reqs(2, rng2))
    assert a == b


# =============================================================================
# Edge cases + bugfix regressions (deterministic fake model)
# =============================================================================
def test_echo_model_sequence():
    out = echo_engine(2).generate(
        [Request(uid=0, prompt=np.array([5], np.int32), max_new_tokens=4)])
    assert out[0] == [6, 7, 8, 9]


def test_partial_wave_masks_ghost_slots():
    """Regression: a partial wave must prefill/decode only its true size —
    pre-fix, zero-padded ghost slots ran the full decode loop."""
    eng = echo_engine(4)
    calls = _count_decodes(eng)
    reqs = [Request(uid=i, prompt=np.array([3 + i], np.int32),
                    max_new_tokens=3) for i in range(2)]
    out = eng.generate(reqs)
    assert out == {0: [4, 5, 6], 1: [5, 6, 7]}
    assert calls, "expected at least one decode step"
    assert all(shape == (2, 1) for shape in calls), calls


def test_partial_wave_matches_full_wave_output_and_steps():
    """A 2-request wave must produce identical output and decode-step count
    whether the engine batch is exactly 2 or has 2 ghost slots."""
    reqs = [Request(uid=i, prompt=np.array([10 + i], np.int32),
                    max_new_tokens=4) for i in range(2)]
    full = echo_engine(2)
    partial = echo_engine(4)
    full_calls = _count_decodes(full)
    partial_calls = _count_decodes(partial)
    out_full = full.generate([dataclasses.replace(r) for r in reqs])
    out_partial = partial.generate([dataclasses.replace(r) for r in reqs])
    assert out_full == out_partial
    assert len(full_calls) == len(partial_calls)


def test_max_new_tokens_zero_gets_no_tokens():
    """Regression: a 0-budget request batched with longer ones received one
    generated token (append ran before the length check)."""
    reqs = [Request(uid=0, prompt=np.array([5], np.int32), max_new_tokens=0),
            Request(uid=1, prompt=np.array([7], np.int32), max_new_tokens=3)]
    out = echo_engine(2).generate(reqs)
    assert out[0] == []
    assert out[1] == [8, 9, 10]


def test_all_zero_budget_wave_never_decodes():
    eng = echo_engine(2)
    calls = _count_decodes(eng)
    out = eng.generate([Request(uid=i, prompt=np.array([4], np.int32),
                                max_new_tokens=0) for i in range(2)])
    assert out == {0: [], 1: []}
    assert calls == []


def test_eos_mid_wave():
    """One request hits EOS early; its slot stops appending while the other
    runs to its full budget."""
    reqs = [Request(uid=0, prompt=np.array([5], np.int32), max_new_tokens=6,
                    eos_id=7),
            Request(uid=1, prompt=np.array([20], np.int32), max_new_tokens=6)]
    out = echo_engine(2).generate(reqs)
    assert out[0] == [6, 7]                        # stops at EOS (included)
    assert out[1] == [21, 22, 23, 24, 25, 26]      # full budget


def test_empty_request_list():
    assert echo_engine(2).generate([]) == {}


def test_engine_warmup_compiles_decode():
    eng = echo_engine(2, max_seq=16)
    calls = _count_decodes(eng)
    eng.warmup()
    assert len(calls) >= 1


# =============================================================================
# The decode loop against a plain per-row loop: same tokens, same decode calls
# =============================================================================
LOOP_MODELS = {
    "echo": EchoModel,
    "qwen-smoke": lambda: build_model(SMOKES["qwen1.5-0.5b"]),
}
# budgets, and {row: the pass whose token (without EOS) becomes its EOS}
LOOP_CASES = {
    "mixed_budgets": ([5, 3, 1, 4], {}),
    "zero_budget": ([0, 4, 2], {}),
    "all_zero": ([0, 0], {}),
    "eos_beside_no_eos": ([6, 6], {0: 2}),
    "all_eos_same_pass": ([6, 6, 6], {0: 3, 1: 3, 2: 3}),
}


@pytest.fixture(scope="module")
def loop_engines():
    """One engine per model, and a jitted plain decode on its weights."""
    out = {}
    for name, make in LOOP_MODELS.items():
        model = make()
        eng = ServeEngine(model, batch_size=4, max_seq=32,
                          rng=jax.random.PRNGKey(3))
        out[name] = (eng, jax.jit(model.decode))
    return out


def _plain_loop(engine, decode, wave):
    """The decode loop as a plain per-row loop: every live row reads its
    token from the device on its own, and a decode follows any pass that
    leaves a row live.  Returns (tokens by row, decode calls)."""
    plen = max(len(r.prompt) for r in wave)
    toks = np.zeros((len(wave), plen), np.int32)
    for i, r in enumerate(wave):
        toks[i, plen - len(r.prompt):] = r.prompt
    logits, cache = engine.model.prefill(
        engine.params, {"tokens": jnp.asarray(toks)}, max_seq=engine.max_seq)
    gen = [[] for _ in wave]
    live = [r.max_new_tokens > 0 for r in wave]
    calls = 0
    while True:
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        for i, r in enumerate(wave):
            if live[i]:
                gen[i].append(int(nxt[i]))
                if gen[i][-1] == r.eos_id or len(gen[i]) >= r.max_new_tokens:
                    live[i] = False
        if not any(live):
            return gen, calls
        logits, cache = decode(engine.params, cache, {"tokens": nxt[:, None]})
        calls += 1


def _loop_wave(budgets, eos=()):
    eos = dict(eos)
    return [Request(uid=i, prompt=(np.arange(3 + i, dtype=np.int32) * 53
                                   + 29 * i) % 500 + 1,
                    max_new_tokens=b, eos_id=eos.get(i, -1))
            for i, b in enumerate(budgets)]


@pytest.mark.parametrize("case", sorted(LOOP_CASES))
@pytest.mark.parametrize("model", sorted(LOOP_MODELS))
def test_decode_loop_matches_plain_per_row_loop(loop_engines, model, case,
                                                monkeypatch):
    eng, decode = loop_engines[model]
    budgets, eos_at = LOOP_CASES[case]
    if eos_at:
        # each EOS row's EOS is its own token at the given pass
        free, _ = _plain_loop(eng, decode, _loop_wave(budgets))
        eos = {i: free[i][p] for i, p in eos_at.items()}
    else:
        eos = {}
    want, want_calls = _plain_loop(eng, decode, _loop_wave(budgets, eos))
    for i, p in eos_at.items():
        assert len(want[i]) == p + 1, (i, want[i])    # the case is as named
    calls = []
    orig = eng._decode

    def counting(*a):
        calls.append(1)
        return orig(*a)

    monkeypatch.setattr(eng, "_decode", counting)
    before = obs.snapshot()
    out = eng.generate(_loop_wave(budgets, eos))
    ahead = (obs.snapshot().get("engine.decode_ahead", 0)
             - before.get("engine.decode_ahead", 0))
    assert [out[i] for i in range(len(budgets))] == want
    assert len(calls) == want_calls
    if len(eos) == len(budgets):
        assert ahead == 0         # every row may end on EOS: nothing ahead
    elif not eos:
        assert ahead == want_calls


@pytest.mark.parametrize("model", sorted(LOOP_MODELS))
def test_engine_decode_lowers_as_jit_decode(loop_engines, model):
    """The benchmark finds the decode program in a trace by this name."""
    eng, _ = loop_engines[model]
    toks = jnp.ones((2, 4), jnp.int32)
    _, cache = eng.model.prefill(eng.params, {"tokens": toks},
                                 max_seq=eng.max_seq)
    lowered = eng._decode.lower(eng.params, cache, {"tokens": toks[:, :1]})
    assert lowered.as_text().startswith("module @jit_decode")


# =============================================================================
# tune_engine_batch: warmup + engine reuse (JIT-bias regression)
# =============================================================================
class _FakeEngine:
    def __init__(self, batch, log, builds):
        self.batch = batch
        self.log = log
        builds[batch] = builds.get(batch, 0) + 1

    def warmup(self):
        self.log.append(("warmup", self.batch))

    def generate(self, requests):
        self.log.append(("generate", self.batch))
        return {r.uid: [] for r in requests}


def test_tune_engine_batch_warms_up_and_reuses_engines():
    """Regression: each trial must serve an untimed warmup wave before its
    timed run (pre-fix, first-call JIT compilation was inside the timed
    region) and engines must be built once per batch size."""
    log, builds = [], {}
    reqs = [Request(uid=i, prompt=np.array([1], np.int32), max_new_tokens=2)
            for i in range(4)]
    best, best_s, hist = tune_engine_batch(
        lambda b: _FakeEngine(b, log, builds), reqs, batch_sizes=(1, 2, 4))
    assert set(builds) == {1, 2, 4} and all(v == 1 for v in builds.values())
    assert len(hist) == 3
    for b in (1, 2, 4):
        events = [kind for kind, eb in log if eb == b]
        assert events[0] == "warmup", (b, events)
        assert events.count("generate") >= 1
