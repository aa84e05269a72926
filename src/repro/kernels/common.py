"""Shared helpers for Pallas TPU kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def mxu_precision(dtype):
    """MXU precision for a kernel dot: float32 operands get full-f32
    passes (one bf16 pass misses f32 references by ~1e-3); narrower
    operands take the native pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def lane_efficiency_2d(bm: int, bn: int, m: int, n: int) -> float:
    """Useful-lane fraction for (bm, bn) tiles over an (m, n) problem.

    Two waste sources on TPU: sublane/lane padding of the tile to the (8, 128)
    register tiling, and edge-tile padding when the block does not divide the
    problem.  This is the warp-execution-efficiency analog (DESIGN.md §2).
    """
    tile_eff = (bm / round_up(bm, 8)) * (bn / round_up(bn, 128))
    edge_eff = (m / round_up(m, bm)) * (n / round_up(n, bn))
    return tile_eff * edge_eff
