"""Attention variants: GQA/MQA (with optional QKV bias) and DeepSeek-style
MLA (multi-head latent attention with compressed KV cache).

Prefill/training uses memory-safe chunked ("flash-style") attention in pure
JAX — the Pallas kernel in repro/kernels/attention is the TPU hot-path
drop-in, validated against the same oracle.  Decode uses a dense matvec over
the KV cache.

Cache layout (GQA):   {"k": (B, S_max, Hkv, D), "v": ..., "pos": int32}
Cache layout (MLA):   {"c_kv": (B, S_max, R), "k_rope": (B, S_max, Dr)}
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (YaRN, ParamDef, Params, apply_rope, dense,
                                 rms_norm)

# §Perf switch: causal upper-triangle block skipping in train/prefill
# attention (see _causal_block_attention).  Module-level so experiments can
# A/B the paper-faithful baseline (False) against the optimized path.
CAUSAL_BLOCK_SKIP = True


class AttnConfig(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    # MLA (deepseek) extras
    kv_lora_rank: int = 0          # 0 => plain GQA
    qk_rope_dim: int = 64
    v_head_dim: int = 0            # defaults to head_dim
    latent_norm: bool = False      # RMSNorm on the latent before caching
    yarn: Optional[YaRN] = None    # rotary scaling of the rope part


# =============================================================================
# GQA
# =============================================================================
def gqa_defs(cfg: AttnConfig) -> Dict[str, ParamDef]:
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    defs = {
        "wq": ParamDef((d, h * hd), ("embed", "heads")),
        "wk": ParamDef((d, hk * hd), ("embed", "kv")),
        "wv": ParamDef((d, hk * hd), ("embed", "kv")),
        "wo": ParamDef((h * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h * hd,), ("heads",), init="zeros")
        defs["bk"] = ParamDef((hk * hd,), ("kv",), init="zeros")
        defs["bv"] = ParamDef((hk * hd,), ("kv",), init="zeros")
    return defs


def _causal_block_attention(
    q: jax.Array,   # (B, S, H, D)
    k: jax.Array,   # (B, S, Hkv, D)
    v: jax.Array,   # (B, S, Hkv, Dv)
    chunk: int,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal attention with upper-triangle block SKIPPING.

    The kv-chunked form computes every (q, kv) block and masks half of them
    — 2x wasted MXU flops and score traffic at long S.  Here q is ALSO
    chunked (python loop, static shapes) and q-chunk i only touches
    kv[: (i+1)*chunk], so skipped blocks are never materialized: flops and
    bytes become triangular (sum i*c^2 ~ S^2/2).  §Perf iteration for the
    attention-dominated cells; the Pallas flash kernel does the same
    skipping on-chip (kernels/attention).
    """
    b, s, h, d = q.shape
    if s % chunk != 0 or s // chunk <= 1:
        return _chunked_attention(q, k, v, True, chunk=chunk, scale=scale)
    nq = s // chunk
    outs = []
    for i in range(nq):
        qi = q[:, i * chunk:(i + 1) * chunk]
        kv_len = (i + 1) * chunk
        outs.append(_chunked_attention(
            qi, k[:, :kv_len], v[:, :kv_len], True,
            q_offset=i * chunk, chunk=chunk, scale=scale))
    return jnp.concatenate(outs, axis=1)


def _chunked_attention(
    q: jax.Array,   # (B, S, H, D)
    k: jax.Array,   # (B, T, Hkv, D)
    v: jax.Array,   # (B, T, Hkv, Dv)
    causal: bool,
    q_offset: int = 0,
    chunk: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Flash-style online-softmax attention chunked over the KV axis; the
    scores are scaled by ``scale``, 1/sqrt(D) where None."""
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    rep = h // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # operands stay in the model dtype; MXU accumulates f32 via
    # preferred_element_type — no f32 copy of K/V ever hits HBM
    qf = (q * scale).reshape(b, s, hkv, rep, d)

    n_chunks = -(-t // chunk)
    pad_t = n_chunks * chunk
    if pad_t != t:
        k = jnp.pad(k, ((0, 0), (0, pad_t - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t - t), (0, 0), (0, 0)))
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, hkv, dv), 1, 0)

    q_pos = q_offset + jnp.arange(s)

    def body(carry, ckv):
        m, l, acc, c_idx = carry
        kb, vb = ckv
        sij = jnp.einsum("bshrd,bthd->bhrst", qf, kb,
                         preferred_element_type=jnp.float32)
        kv_pos = c_idx * chunk + jnp.arange(chunk)
        mask = kv_pos[None, :] < t
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        sij = jnp.where(mask[None, None, None], sij, -1e30)
        m_new = jnp.maximum(m, jnp.max(sij, axis=-1))
        p = jnp.exp(sij - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhrst,bthv->bhrsv", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc, c_idx + 1), None

    m0 = jnp.full((b, hkv, rep, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, rep, s, dv), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, 0), (kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(b, s, h, dv)
    return out


def gqa_apply(
    p: Params,
    cfg: AttnConfig,
    x: jax.Array,                       # (B, S, D)
    positions: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,       # decode: append + attend over cache
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Optional[Dict]]:
    b, s, _ = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    k = dense(x, p["wk"], p.get("bk")).reshape(b, s, hk, hd)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, s, hk, hd)

    if cache is None:
        pos = positions if positions is not None else jnp.arange(s)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        if cfg.causal and CAUSAL_BLOCK_SKIP:
            out = _causal_block_attention(q, k, v, chunk=kv_chunk)
        else:
            out = _chunked_attention(q, k, v, cfg.causal, chunk=kv_chunk)
        new_cache = None
    else:
        # single-token decode: attend over the stored prefix plus the current
        # token WITHOUT rewriting the cache — the caller batches all layers'
        # new K/V into one stacked cache update (in-place, outside the layer
        # scan), so per-step cache traffic is read + one token-slot write.
        pos = cache["pos"]                          # scalar int32
        q = apply_rope(q, pos[None, None], cfg.rope_theta)
        k = apply_rope(k, pos[None, None], cfg.rope_theta)
        kc, vc = cache["k"], cache["v"]
        t = kc.shape[1]
        rep = h // hk
        scale = 1.0 / math.sqrt(hd)
        qf = (q * scale).reshape(b, 1, hk, rep, hd)
        sij = jnp.einsum("bshrd,bthd->bhrst", qf, kc,
                         preferred_element_type=jnp.float32)
        valid = jnp.arange(t)[None, :] < pos
        sij = jnp.where(valid[None, None, None], sij, -1e30)
        s_self = jnp.einsum("bshrd,bshd->bhrs", qf, k,
                            preferred_element_type=jnp.float32)
        sij = jnp.concatenate([sij, s_self[..., None]], axis=-1)
        pr = jax.nn.softmax(sij, axis=-1)
        out = jnp.einsum("bhrst,bthv->bhrsv",
                         pr[..., :t].astype(vc.dtype), vc,
                         preferred_element_type=jnp.float32)
        # current token's value contribution: (B,Hk,rep,1,1)·(B,Hk,1,1,Dv)
        v_self = v[:, 0][:, :, None, None, :]
        out = out + pr[..., -1][..., None] * v_self.astype(jnp.float32)
        out = jnp.moveaxis(out, 3, 1).reshape(b, 1, h, hd)
        new_cache = {"k_new": k, "v_new": v}

    y = dense(out.reshape(b, s, h * hd).astype(x.dtype), p["wo"])
    return y, new_cache


def gqa_init_cache(cfg: AttnConfig, batch: int, max_seq: int, dtype):
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_seq, hk, hd), dtype),
        "v": jnp.zeros((batch, max_seq, hk, hd), dtype),
        "pos": jnp.int32(0),
    }


# =============================================================================
# MLA (DeepSeek-V2): compressed KV latent + decoupled RoPE key
# =============================================================================
def mla_defs(cfg: AttnConfig) -> Dict[str, ParamDef]:
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    dv = cfg.v_head_dim or hd
    defs = {
        # queries: nope part + rope part per head
        "wq": ParamDef((d, h * (hd + dr)), ("embed", "heads")),
        # KV joint compression to rank r; decompression to K_nope and V
        "w_dkv": ParamDef((d, r), ("embed", None)),
        "w_uk": ParamDef((r, h * hd), (None, "heads")),
        "w_uv": ParamDef((r, h * dv), (None, "heads")),
        # shared (per-token, head-agnostic) rotary key
        "w_kr": ParamDef((d, dr), ("embed", None)),
        "wo": ParamDef((h * dv, d), ("heads", "embed")),
    }
    if cfg.latent_norm:
        defs["kv_ln"] = ParamDef((r,), (None,), init="ones")
    return defs


def mla_apply(
    p: Params,
    cfg: AttnConfig,
    x: jax.Array,
    positions: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Optional[Dict]]:
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    dv = cfg.v_head_dim or hd

    q = dense(x, p["wq"]).reshape(b, s, h, hd + dr)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    c_kv = dense(x, p["w_dkv"])                     # (B, S, R) — the cache
    if cfg.latent_norm:
        c_kv = rms_norm(c_kv, p["kv_ln"])
    k_rope = dense(x, p["w_kr"])                    # (B, S, Dr) shared
    # scores are divided by ``denom``: sqrt(hd + dr), over YaRN's factor
    denom = math.sqrt(hd + dr) / (cfg.yarn.softmax_factor
                                  if cfg.yarn is not None else 1.0)

    def rope(t, pos):
        return apply_rope(t, pos, cfg.rope_theta, cfg.yarn)

    if cache is None:
        pos = positions if positions is not None else jnp.arange(s)[None, :]
        q_rope = rope(q_rope, pos)
        k_rope_r = rope(k_rope[:, :, None, :], pos)
        k_nope = dense(c_kv, p["w_uk"]).reshape(b, s, h, hd)
        v = dense(c_kv, p["w_uv"]).reshape(b, s, h, dv)
        # concatenated effective head dims: [nope | rope]
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope_r, (b, s, h, dr))], axis=-1
        )
        if cfg.causal and CAUSAL_BLOCK_SKIP:
            out = _causal_block_attention(q_full, k_full, v, chunk=kv_chunk,
                                          scale=1.0 / denom)
        else:
            out = _chunked_attention(q_full, k_full, v, cfg.causal,
                                     chunk=kv_chunk, scale=1.0 / denom)
        # what a decode reads: the normed latent and the rotated key
        new_cache = {"c_kv": c_kv, "k_rope": k_rope_r[:, :, 0, :]}
    else:
        pos = cache["pos"]
        q_rope = rope(q_rope, pos[None, None])
        k_rope_r = rope(k_rope[:, :, None, :], pos[None, None])[:, :, 0, :]
        ckv_c, kr_c = cache["c_kv"], cache["k_rope"]
        t = ckv_c.shape[1]
        # absorbed attention: score = q_nope^T W_uk c_kv + q_rope^T k_rope
        wk = p["w_uk"].reshape(r, h, hd)
        q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wk,
                           preferred_element_type=jnp.float32)  # (B,1,H,R)
        s_nope = jnp.einsum("bshr,btr->bhst", q_abs.astype(ckv_c.dtype),
                            ckv_c, preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bshd,btd->bhst", q_rope, kr_c,
                            preferred_element_type=jnp.float32)
        sij = (s_nope + s_rope) / denom
        valid = jnp.arange(t)[None, :] < pos
        sij = jnp.where(valid[None, None], sij, -1e30)
        # current token's own score (cache not yet updated)
        s_self = (jnp.einsum("bshr,bsr->bhs", q_abs.astype(c_kv.dtype),
                             c_kv, preferred_element_type=jnp.float32)
                  + jnp.einsum("bshd,bsd->bhs", q_rope, k_rope_r,
                               preferred_element_type=jnp.float32)
                  ) / denom
        sij = jnp.concatenate([sij, s_self[..., None]], axis=-1)
        pr = jax.nn.softmax(sij, axis=-1)
        ctx = jnp.einsum("bhst,btr->bshr",
                         pr[..., :t].astype(ckv_c.dtype), ckv_c,
                         preferred_element_type=jnp.float32)
        ctx = ctx + jnp.einsum("bhs,bsr->bshr", pr[..., -1],
                               c_kv.astype(jnp.float32))[:, :, :, :]
        wv = p["w_uv"].reshape(r, h, dv)
        out = jnp.einsum("bshr,rhd->bshd", ctx.astype(wv.dtype), wv,
                         preferred_element_type=jnp.float32)
        new_cache = {"c_kv_new": c_kv, "k_rope_new": k_rope_r}

    y = dense(out.reshape(b, s, h * dv).astype(x.dtype), p["wo"])
    return y, new_cache


def mla_init_cache(cfg: AttnConfig, batch: int, max_seq: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype),
        "pos": jnp.int32(0),
    }
