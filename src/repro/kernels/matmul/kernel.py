"""Tiled GEMM Pallas TPU kernel (paper benchmark: GEMM / GEMM-full).

Grid (m, n, k) with k innermost-sequential; fp32 accumulator lives in VMEM
scratch across the k steps (standard MXU blocking: HBM→VMEM tiles sized by
BlockSpec, MXU consumes (BM, BK) x (BK, BN)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, mxu_precision


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                   k_total: int, block_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if k_total % block_k != 0:
        # mask the K tail: the last tile reads past the array bound and the
        # pad contents are undefined (NaN in interpret mode)
        k_idx = pl.program_id(2) * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k,), 0
        )
        valid = k_idx < k_total
        a = jnp.where(valid[None, :], a, 0)
        b = jnp.where(valid[:, None], b, 0)

    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32,
                            precision=mxu_precision(a.dtype))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "loop_order", "interpret"),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    loop_order: str = "mnk",
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.

    loop_order 'mnk' iterates m outermost (better A reuse when N is small);
    'nmk' iterates n outermost (better B reuse when M is small).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    k_steps = cdiv(k, block_k)

    if loop_order == "mnk":
        grid = (cdiv(m, block_m), cdiv(n, block_n), k_steps)
        a_map = lambda i, j, kk: (i, kk)
        b_map = lambda i, j, kk: (kk, j)
        o_map = lambda i, j, kk: (i, j)
    elif loop_order == "nmk":
        grid = (cdiv(n, block_n), cdiv(m, block_m), k_steps)
        a_map = lambda j, i, kk: (i, kk)
        b_map = lambda j, i, kk: (kk, j)
        o_map = lambda j, i, kk: (i, j)
    else:
        raise ValueError(loop_order)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps, k_total=k,
                          block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), a_map),
            pl.BlockSpec((block_k, block_n), b_map),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
