"""The one traffic generator: reads a workload file's parameters, draws from --seed.

A workload file (``bench/workloads/<cell>.json``) names its mixes, each a
prompt length and a clipped log-normal answer length, the phases of the window
that use each mix, and the loop that offers them:

* ``{"kind": "open", "rate_per_s": r}``: a Poisson schedule of ``r`` requests
  a second, due whether or not earlier ones finished;
* ``{"kind": "closed", "clients": c}``: ``c`` clients, each sending its next
  request the moment the previous one is answered.

Every seed gets the same sizes and the same arrivals in the same order:
answer lengths are the quantiles of their distribution and arrival gaps the
quantiles of the exponential, each permuted by a generator of its own with
the fixed seed ``ORDER_SEED``.  The run's seed draws the prompt tokens.
The order of a Poisson schedule decides its bursts, and with them the tail
latency, so the run's seed leaves it alone.

Each mix has one prompt length: the serve engine left-pads a wave to its
longest prompt and attends to the padding, so prompts of different lengths
in one wave get answers of other prompts (``bench/probe_left_pad.py``).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import numpy as np

ORDER_SEED = 0

def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def answer_pool(spec: dict, n: int) -> np.ndarray:
    """The clipped log-normal answer lengths at ``n`` evenly spaced quantiles."""
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in quantiles(n)])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


class Traffic:
    """Request sizes, tokens and arrivals of one run of one cell."""

    def __init__(self, wl: dict, vocab: int, seconds: float, seed: int):
        self.wl = wl
        self.vocab = int(vocab)
        self.seconds = float(seconds)
        self.rng = np.random.default_rng(int(seed))
        self.order = np.random.default_rng(ORDER_SEED)
        self.mixes: Dict[str, dict] = wl["mixes"]
        loop = wl["loop"]
        # answer lengths come in blocks, each a fresh permutation of the same
        # quantiles: the whole schedule of an open loop, one request per
        # client of a closed one
        self.block = (self.count() if loop["kind"] == "open"
                      else int(loop["clients"]))
        self._blocks: Dict[Tuple[str, str], list] = {}

    def mix_at(self, t: float) -> str:
        """The mix of a request due ``t`` seconds into the window."""
        name = self.wl["phases"][0]["mix"]
        for ph in self.wl["phases"]:
            if t >= ph["from"] * self.seconds:
                name = ph["mix"]
        return name

    def window_mixes(self) -> List[str]:
        return sorted({ph["mix"] for ph in self.wl["phases"]})

    def draw(self, mix: str, stream: str = "window"
             ) -> Tuple[np.ndarray, int]:
        """(prompt tokens, answer length) of the next request of ``mix``.
        Set-up draws from a stream of its own, so that every seed's window
        sees the same blocks."""
        m = self.mixes[mix]
        block = self._blocks.setdefault((stream, mix), [])
        if not block:
            block.extend(self.order.permutation(
                answer_pool(m["answer"], self.block)))
        prompt = self.rng.integers(1, self.vocab, size=int(m["prompt_len"]),
                                   dtype=np.int32)
        return prompt, int(block.pop())

    def count(self) -> int:
        """Requests an open loop sends in the window."""
        return max(1, int(round(self.wl["loop"]["rate_per_s"]
                                * self.seconds)))

    def schedule(self) -> List[float]:
        """Due times (s from the window's start) of an open loop: the rate
        times the window's length requests, with gaps at the quantiles of
        the exponential, permuted, and scaled to end inside the window."""
        n = self.count()
        gaps = self.order.permutation(-np.log1p(-quantiles(n)))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return list(due * self.seconds / gaps.sum())
