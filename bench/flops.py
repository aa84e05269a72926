"""Operations and bytes of a dense decoder's work, from its shapes alone.

Model FLOPs count multiply-adds as two operations over the matrix products the
algorithm needs, attention included, at the published vocabulary; recomputed
work and padding do not count.  ``m`` is ``lm.dims(config)``.

Hand count for qwen1.5-0.5b (24 layers, d 1024, 16 heads of 64, MHA, ffn 2816,
vocab 151936, tied):
  per layer 4 * 1024 * 1024 (q, k, v, o) + 3 * 1024 * 2816 = 12,845,056
  24 layers 308,281,344; head 1024 * 151936 = 155,582,464
  matrix parameters 463,863,808; with norms (50,176) and q/k/v biases
  (73,728) 463,987,712 parameters.
Hand count for qwen2.5-3b (36 layers, d 2048, 16 q heads and 2 kv heads of
128, ffn 11008, vocab 151936, tied):
  per layer 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008 = 77,070,336
  36 layers 2,774,532,096; head 2048 * 151936 = 311,164,928
  matrix parameters 3,085,697,024.
"""
from __future__ import annotations

from typing import Iterable

HAND_MATRIX_PARAMS = {"qwen1.5-0.5b": 463_863_808, "qwen2.5-3b": 3_085_697_024}
HAND_PARAMS = {"qwen1.5-0.5b": 463_987_712}


def layer_matrix_params(m: dict) -> int:
    q = m["heads"] * m["head_dim"]
    kv = m["kv_heads"] * m["head_dim"]
    return m["d"] * (2 * q + 2 * kv) + 3 * m["d"] * m["ff"]


def head_params(m: dict) -> int:
    return m["d"] * m["vocab"]


def matrix_params(m: dict) -> int:
    return m["layers"] * layer_matrix_params(m) + head_params(m)


def attention_flops(m: dict, keys: float) -> float:
    """Scores and weighted values of one query over ``keys`` keys, all
    layers."""
    return 4.0 * m["layers"] * m["heads"] * m["head_dim"] * keys


def prefill_flops(m: dict, prompt: int) -> float:
    """One causal prompt of ``prompt`` tokens, logits at its last position."""
    p = float(prompt)
    return (2.0 * m["layers"] * layer_matrix_params(m) * p
            + 2.0 * head_params(m) + attention_flops(m, p * (p + 1) / 2))


def decode_flops(m: dict, position: int) -> float:
    """One generated token at ``position`` (it attends position + 1 keys)."""
    return 2.0 * matrix_params(m) + attention_flops(m, position + 1)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (three forwards) per token of a causal sequence."""
    return 3.0 * (2.0 * matrix_params(m)
                  + attention_flops(m, (seq + 1) / 2.0))


def kv_bytes_per_position(m: dict, bytes_per_value: int = 2) -> int:
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * bytes_per_value


def decode_bytes(m: dict, params: int, positions: Iterable[int],
                 bytes_per_value: int = 2) -> float:
    """Least HBM traffic of one decode step: every weight read once, the
    cache of the positions in use read and one new position written per
    row."""
    kv = kv_bytes_per_position(m, bytes_per_value)
    return float(params) * bytes_per_value + sum(kv * (p + 1)
                                                 for p in positions)
