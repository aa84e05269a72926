"""The traffic generator: one seed, one set of inputs; every seed, the same
sizes and arrivals in the same order, with prompts of its own."""
import numpy as np

from bench import traffic

WL = {
    "loop": {"kind": "open", "rate_per_s": 7.0},
    "phases": [{"from": 0.0, "mix": "chat"}, {"from": 0.5, "mix": "docs"}],
    "mixes": {"chat": {"prompt_len": 12, "answer": {
                  "median": 16, "sigma": 0.5, "min": 4, "max": 32}},
              "docs": {"prompt_len": 30, "answer": {
                  "median": 12, "sigma": 0.3, "min": 8, "max": 16}}},
}


def draws(seed, n=40):
    t = traffic.Traffic(WL, 1000, 10.0, seed)
    due = t.schedule()
    reqs = [t.draw(t.mix_at(d)) for d in due[:n]]
    return due, reqs


def test_same_seed_same_inputs():
    a, b = draws(2**33 + 5), draws(2**33 + 5)
    assert a[0] == b[0]
    assert all(np.array_equal(p, q) and x == y
               for (p, x), (q, y) in zip(a[1], b[1]))


def test_seeds_share_sizes_and_arrivals():
    t1, t2 = (traffic.Traffic(WL, 1000, 10.0, s) for s in (1, 2))
    d1, d2 = t1.schedule(), t2.schedule()
    assert len(d1) == 70
    assert d1 == d2
    assert all(0.0 <= d < 10.0 for d in d1)
    assert not np.allclose(np.diff(d1), np.diff(d1).mean())
    r1 = [t1.draw("chat") for _ in range(70)]
    r2 = [t2.draw("chat") for _ in range(70)]
    a1, a2 = [a for _, a in r1], [a for _, a in r2]
    assert a1 == a2 and len(set(a1)) > 1
    assert min(a1) >= 4 and max(a1) <= 32
    assert not any(np.array_equal(p, q) for (p, _), (q, _) in zip(r1, r2))


def test_phases_and_prompt_lengths():
    t = traffic.Traffic(WL, 1000, 10.0, 0)
    assert t.mix_at(0.0) == "chat" and t.mix_at(5.0) == "docs"
    prompt, ans = t.draw("docs")
    assert prompt.shape == (30,) and prompt.min() >= 1 and prompt.max() < 1000
    assert 8 <= ans <= 16
