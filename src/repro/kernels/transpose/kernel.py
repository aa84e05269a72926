"""Tiled matrix transpose Pallas TPU kernel (paper benchmark: Transpose).

Memory-bound: each program stages a (BM, BN) tile through VMEM and writes the
transposed (BN, BM) tile.  The GPU original tunes shared-memory tiles and
padding (bank conflicts); the TPU analog tunes VMEM tile shape — sublane/lane
alignment of *both* the read and the write tile is the performance axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _transpose_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def transpose(
    x: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    m, n = x.shape
    grid = (cdiv(m, block_m), cdiv(n, block_n))
    return pl.pallas_call(
        _transpose_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x)
