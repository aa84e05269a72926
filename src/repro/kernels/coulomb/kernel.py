"""Direct Coulomb Summation Pallas TPU kernel (paper §2 running example).

Electrostatic potential on a regular 3D grid: V_i = Σ_j w_j / r_ij.
One program computes a (Z_IT, BY, BX) block of grid points — Z_IT is the
thread-coarsening tuning parameter from the paper's Listing 1, mapped to TPU
grid-point coarsening along z (the register-locality trade-off is identical:
larger Z_IT reuses each atom across more grid points but grows the VMEM
accumulator and reduces program-level parallelism).

Atoms are processed in ATOM_CHUNK tiles via a sequential grid dimension.
The flat atom table sits in scalar memory (SMEM, 1 MiB on a v5e: up to ~64k
atoms) and the kernel loops over its tile's atoms, broadcasting one atom's
scalars against the whole (Z_IT, BY, BX) block — the layout of the GPU
original, where each thread reads atoms from constant memory.  The wrapper
zero-pads the table to whole tiles (weight 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _coulomb_kernel(
    atoms_ref, out_ref, acc_ref, *,
    a_steps: int, atom_chunk: int, z_it: int, by: int, bx: int,
    spacing: float,
):
    z0 = pl.program_id(0) * z_it
    y0 = pl.program_id(1) * by
    x0 = pl.program_id(2) * bx

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # real-space coordinates of this block of grid points: (Z, BY, BX);
    # Mosaic's iota is integer-only, so index in int32 and cast
    def coord(start, axis):
        idx = start + jax.lax.broadcasted_iota(jnp.int32, (z_it, by, bx), axis)
        return idx.astype(jnp.float32) * spacing

    fz, fy, fx = coord(z0, 0), coord(y0, 1), coord(x0, 2)

    base = pl.program_id(3) * (atom_chunk * 4)

    def atom(a, acc):
        # atoms_ref is the flat padded table: x, y, z, w per atom
        i = base + 4 * a
        dx = fx - atoms_ref[i]
        dy = fy - atoms_ref[i + 1]
        dz = fz - atoms_ref[i + 2]
        r2 = dx * dx + dy * dy + dz * dz
        return acc + atoms_ref[i + 3] * jax.lax.rsqrt(jnp.maximum(r2, 1e-12))

    acc_ref[...] += jax.lax.fori_loop(
        0, atom_chunk, atom, jnp.zeros((z_it, by, bx), jnp.float32))

    @pl.when(pl.program_id(3) == a_steps - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("grid_size", "z_it", "by", "bx", "atom_chunk",
                     "spacing", "interpret"),
)
def coulomb(
    atoms: jax.Array,  # (n_atoms, 4) float32: x, y, z, w
    *,
    grid_size: int,
    z_it: int = 4,
    by: int = 8,
    bx: int = 128,
    atom_chunk: int = 32,
    spacing: float = 0.5,
    interpret: bool = False,
) -> jax.Array:
    n_atoms = atoms.shape[0]
    a_steps = cdiv(n_atoms, atom_chunk)
    # zero-weight padding atoms contribute 0 * finite: no in-kernel mask
    flat = jnp.pad(atoms, ((0, a_steps * atom_chunk - n_atoms), (0, 0)))
    flat = flat.reshape(-1)
    gs = grid_size
    grid = (cdiv(gs, z_it), cdiv(gs, by), cdiv(gs, bx), a_steps)
    return pl.pallas_call(
        functools.partial(
            _coulomb_kernel, a_steps=a_steps, atom_chunk=atom_chunk,
            z_it=z_it, by=by, bx=bx, spacing=spacing,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # whole atom table
        ],
        out_specs=pl.BlockSpec(
            (z_it, by, bx), lambda z, y, x, a: (z, y, x)
        ),
        out_shape=jax.ShapeDtypeStruct((gs, gs, gs), jnp.float32),
        scratch_shapes=[pltpu.VMEM((z_it, by, bx), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(flat)
