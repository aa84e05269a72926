"""N-body gravitational acceleration Pallas TPU kernel (paper benchmark).

a_i = Σ_j G·m_j·(p_j − p_i) / (|p_j − p_i|² + ε²)^{3/2}

One program owns a (BLOCK_I, 4) tile of bodies and accumulates accelerations
while marching over all bodies in (BLOCK_J, 4) tiles on a sequential grid
dimension — the classic compute-bound O(N²) kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _nbody_kernel(
    bi_ref, bjt_ref, out_ref, acc_ref, *,
    j_steps: int, n_bodies: int, block_j: int, softening: float,
):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bi = bi_ref[...]  # (BI, 4): x, y, z, m — one body per sublane row
    # the j tile arrives transposed, (4, BJ): one body per lane, so every
    # pairwise term is a (BI, 1) x (1, BJ) broadcast with no relayout
    j_idx = pl.program_id(1) * block_j + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_j), 1
    )
    # zero the whole tail tile: padded lanes hold undefined values (NaN in
    # interpret mode) and even mass-masked NaN positions would poison s*dx
    bjt = jnp.where(j_idx < n_bodies, bjt_ref[...], 0.0)

    # pairwise displacement: (BI, BJ)
    dx = bjt[0:1, :] - bi[:, 0:1]
    dy = bjt[1:2, :] - bi[:, 1:2]
    dz = bjt[2:3, :] - bi[:, 2:3]
    r2 = dx * dx + dy * dy + dz * dz + softening
    inv_r = jax.lax.rsqrt(r2)
    s = bjt[3:4, :] * inv_r * inv_r * inv_r  # (BI, BJ)

    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
    acc = jnp.zeros(acc_ref.shape, jnp.float32)
    for c, d in enumerate((dx, dy, dz)):
        acc = jnp.where(lane == c, jnp.sum(s * d, axis=1, keepdims=True), acc)
    acc_ref[...] += acc

    @pl.when(pl.program_id(1) == j_steps - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_i", "block_j", "softening", "interpret"),
)
def nbody(
    bodies: jax.Array,  # (N, 4) float32: x, y, z, mass
    *,
    block_i: int = 256,
    block_j: int = 256,
    softening: float = 1e-3,
    interpret: bool = False,
) -> jax.Array:
    n = bodies.shape[0]
    j_steps = cdiv(n, block_j)
    grid = (cdiv(n, block_i), j_steps)
    return pl.pallas_call(
        functools.partial(
            _nbody_kernel, j_steps=j_steps, n_bodies=n, block_j=block_j,
            softening=softening,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((4, block_j), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_i, 4), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 4), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_i, 4), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bodies, bodies.T)
