"""Flash-attention Pallas TPU kernel (framework hot path, 6th tuning space).

Online-softmax blockwise attention for one (S, D) head: grid (q_blocks,
kv_blocks) with the kv dimension sequential; running max/denominator and the
output accumulator live in VMEM scratch.  Batch/head dims are vmapped by the
wrapper in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, mxu_precision

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
    kv_steps: int, block_q: int, block_k: int, seq_len: int,
    sm_scale: float, causal: bool,
):
    qi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        q = q_ref[...]  # (BQ, D)
        k = k_ref[...]  # (BK, D)
        v = v_ref[...]  # (BK, D)
        if seq_len % block_k != 0:
            # zero the kv tail: OOB block rows are undefined (NaN in
            # interpret mode) and 0-probability × NaN would poison the acc
            kv_valid = (ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k,), 0)) < seq_len
            k = jnp.where(kv_valid[:, None], k, 0)
            v = jnp.where(kv_valid[:, None], v, 0)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=mxu_precision(q.dtype)) * sm_scale

        # mask: kv-tail padding + causal upper triangle
        k_idx = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_idx < seq_len
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask &= k_idx <= q_idx
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                      # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                   # (BQ, BK)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32,
            precision=mxu_precision(v.dtype))
        m_ref[...] = m_new

    if causal:
        # skip fully-masked kv blocks above the diagonal
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(body)
    else:
        body()

    @pl.when(ki == kv_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "sm_scale", "interpret"),
)
def flash_attention_single_head(
    q: jax.Array,  # (S, D)
    k: jax.Array,  # (S, D)
    v: jax.Array,  # (S, D)
    *,
    block_q: int = 256,
    block_k: int = 256,
    causal: bool = True,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    s, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    kv_steps = cdiv(s, block_k)
    grid = (cdiv(s, block_q), kv_steps)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, kv_steps=kv_steps, block_q=block_q,
            block_k=block_k, seq_len=s, sm_scale=sm_scale, causal=causal,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


def flash_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,
    v: jax.Array,
    **kw,
) -> jax.Array:
    f = functools.partial(flash_attention_single_head, **kw)
    return jax.vmap(jax.vmap(f))(q, k, v)
