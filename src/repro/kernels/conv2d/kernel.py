"""2D convolution Pallas TPU kernel (paper benchmark: Convolution).

Stencil with halo: BlockSpec tiling cannot express overlapping reads, so the
input stays in HBM (``memory_space=ANY``) and each program DMAs its
(BY + F - 1, BX + F - 1) halo tile, rounded up to the (8, 128) tiling, into
VMEM scratch explicitly (``pltpu.make_async_copy``) — the production TPU
pattern for halo exchange.  The F×F filter sits in SMEM; its taps run as
shifted multiply-accumulates on the VPU, unrolled (UNROLL_TAPS=1) or with
the F filter rows in a loop (UNROLL_TAPS=0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, round_up


def _conv2d_kernel(
    img_ref,    # padded image in HBM/ANY — pre-padded by wrapper
    flt_ref,    # (F, F) in SMEM
    out_ref,    # (BY, BX) block in VMEM
    tile_ref,   # scratch: halo tile rounded up to the (8, 128) tiling
    sem,        # DMA semaphore
    *, by: int, bx: int, f: int, unroll_taps: bool,
):
    i, j = pl.program_id(0), pl.program_id(1)
    th, tw = tile_ref.shape
    # the DMA moves a tile-aligned window (offsets are multiples of BY/BX,
    # extents rounded up); the wrapper pads the image so it stays in bounds
    copy = pltpu.make_async_copy(
        img_ref.at[pl.ds(i * by, th), pl.ds(j * bx, tw)], tile_ref, sem)
    copy.start()
    copy.wait()

    if unroll_taps:
        acc = jnp.zeros((by, bx), jnp.float32)
        for dy in range(f):
            for dx in range(f):
                acc += flt_ref[dy, dx] * tile_ref[dy:dy + by, dx:dx + bx]
    else:
        # Mosaic slices sublanes only at multiples of 8, so a loop-carried
        # row offset rotates the tile up by dy instead of slicing at dy
        def row(dy, acc):
            rows = pltpu.roll(tile_ref[...], th - dy, 0)[:by]
            for dx in range(f):
                acc += flt_ref[dy, dx] * rows[:, dx:dx + bx]
            return acc
        acc = jax.lax.fori_loop(0, f, row, jnp.zeros((by, bx), jnp.float32))
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("by", "bx", "unroll_taps", "interpret")
)
def conv2d(
    img: jax.Array,   # (H, W) float32
    flt: jax.Array,   # (F, F) float32, F odd
    *,
    by: int = 128,
    bx: int = 256,
    unroll_taps: bool = True,
    interpret: bool = False,
) -> jax.Array:
    h, w = img.shape
    f = flt.shape[0]
    assert flt.shape == (f, f) and f % 2 == 1
    halo = f - 1
    th, tw = round_up(by + halo, 8), round_up(bx + halo, 128)
    ny, nx = cdiv(h, by), cdiv(w, bx)
    # "same" convolution: halo // 2 zeros in front; behind, enough that the
    # last program's aligned (th, tw) window is in bounds
    img_p = jnp.pad(img, ((halo // 2, (ny - 1) * by + th - h - halo // 2),
                          (halo // 2, (nx - 1) * bx + tw - w - halo // 2)))
    return pl.pallas_call(
        functools.partial(_conv2d_kernel, by=by, bx=bx, f=f,
                          unroll_taps=unroll_taps),
        grid=(ny, nx),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # stays in HBM
            pl.BlockSpec(memory_space=pltpu.SMEM),    # filter scalars
        ],
        out_specs=pl.BlockSpec((by, bx), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((th, tw), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(img_p, flt)
