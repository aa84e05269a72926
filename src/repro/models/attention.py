"""Attention variants and the serving cache they own: GQA/MQA (with optional
QKV bias) and DeepSeek-style MLA (multi-head latent attention with a
compressed KV cache).

Prefill/training uses memory-safe chunked ("flash-style") attention in pure
JAX, skipping the causal upper triangle block by block — the Pallas kernel in
repro/kernels/attention is the TPU hot-path drop-in, validated against the
same oracle.  Decode uses a dense matvec over the KV cache.

The serving cache is laid out, filled and written here only; the model
modules ask for it through ``attn_init_cache``, ``cache_from_prefill`` and
``write_token`` and never name its entries.  One layer's cache:

    GQA: {"k": (B, Hkv/g, S_max, g*D), "v": (B, Hkv/g, S_max, g*D),
          "pos": int32}
    MLA: {"c_kv": (B, S_max, R), "k_rope": (B, S_max, Dr), "pos": int32}

``k`` is stored rotated; ``c_kv`` is the (normed) latent and ``k_rope`` the
rotated shared key.  ``pos`` is the number of filled positions.  In every
leaf the sequence is the second-minor axis, so a token's entry is one row.
A GQA row holds ``g = kv_group(cfg)`` kv heads side by side (kv head
``j*g + i`` of row group ``j`` in lanes ``i*D:(i+1)*D``), as many as fill a
128-lane vector row: with D < 128 the sequence would otherwise be the
axis the compiler lays on the lanes, and a token's write would touch every
tile of the cache.

``attn_apply`` returns (output, entries), the entries under the cache's own
keys and in its layout: without a cache (prefill) those of the S positions
it attended over, (B, ..., S, W); with one (decode) the new token's,
(B, ..., 1, W), which it attends to without writing the cache.
``cache_from_prefill`` pads a prefill's entries to ``S_max`` and sets
``pos``; ``write_token`` writes a scan's stacked new-token entries of every
layer into the stacked cache (leaves (L, B, ..., S_max, W)) at ``pos`` in
one in-place row write, and advances ``pos``.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (YaRN, ParamDef, Params, apply_rope, dense,
                                 rms_norm)


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


def _attend(cfg: AttnConfig, q: jax.Array, k: jax.Array, v: jax.Array,
            chunk: int, scale: Optional[float] = None) -> jax.Array:
    """Prefill/train attention of S queries over the same S positions:
    causal skips the upper triangle; the encoder's attends everywhere."""
    if cfg.causal:
        return _causal_block_attention(q, k, v, chunk=chunk, scale=scale)
    return _chunked_attention(q, k, v, False, chunk=chunk, scale=scale)


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


def kv_group(cfg: AttnConfig) -> int:
    """``g``, the kv heads a GQA cache row holds: the largest divisor of
    ``n_kv_heads`` with g * head_dim <= 128 (1 where head_dim >= 128)."""
    hk = cfg.n_kv_heads
    return max((g for g in range(1, hk + 1)
                if hk % g == 0 and g * cfg.head_dim <= 128), default=1)


def _to_rows(cfg: AttnConfig, t: jax.Array) -> jax.Array:
    """(B, S, Hkv, D) -> the cache's layout (B, Hkv/g, S, g*D)."""
    b, s, hk, hd = t.shape
    g = kv_group(cfg)
    return jnp.swapaxes(t.reshape(b, s, hk // g, g * hd), 1, 2)


def gqa_apply(
    p: Params,
    cfg: AttnConfig,
    x: jax.Array,                       # (B, S, D)
    positions: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,       # decode: attend over cache + token
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Dict]:
    b, s, _ = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    k = dense(x, p["wk"], p.get("bk")).reshape(b, s, hk, hd)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, s, hk, hd)

    if cache is None:
        pos = positions if positions is not None else jnp.arange(s)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        out = _attend(cfg, q, k, v, kv_chunk)
        k, v = _to_rows(cfg, k), _to_rows(cfg, v)
    else:
        # single-token decode: attend over the stored prefix plus the current
        # token WITHOUT rewriting the cache — ``write_token`` writes all
        # layers' new K/V once, in place, outside the layer scan, so per-step
        # cache traffic is read + one row write.
        pos = cache["pos"]                          # scalar int32
        q = apply_rope(q, pos[None, None], cfg.rope_theta)
        k = _to_rows(cfg, apply_rope(k, pos[None, None], cfg.rope_theta))
        v = _to_rows(cfg, v)                        # (B, Hkv/g, 1, g*D)
        kc, vc = cache["k"], cache["v"]             # (B, Hkv/g, S_max, g*D)
        t = kc.shape[2]
        g, rep = kv_group(cfg), h // hk
        rows = hk // g
        # the cache is read in place: each query head's vector sits in its
        # kv head's D lanes of the row, zeros elsewhere (block-diagonal q),
        # and keeps those D lanes of the weighted values
        blocks = jnp.eye(g, dtype=q.dtype)[:, None, :, None]
        qf = q * (1.0 / math.sqrt(hd))
        qb = (qf.reshape(b, rows, g, rep, 1, hd) * blocks).reshape(
            b, rows, g * rep, g * hd)
        sij = jnp.einsum("bjqw,bjtw->bjqt", qb, kc,
                         preferred_element_type=jnp.float32)
        valid = jnp.arange(t) < pos
        sij = jnp.where(valid, sij, -1e30)
        s_self = jnp.einsum("bjqw,bjsw->bjqs", qb, k,
                            preferred_element_type=jnp.float32)
        pr = jax.nn.softmax(jnp.concatenate([sij, s_self], axis=-1), axis=-1)
        out = jnp.einsum("bjqt,bjtw->bjqw", pr[..., :t].astype(vc.dtype), vc,
                         preferred_element_type=jnp.float32)
        # current token's value: (B,Hkv/g,g*rep,1)·(B,Hkv/g,1,g*D)
        out = out + pr[..., t:] * v.astype(jnp.float32)
        out = jnp.sum(out.reshape(b, rows, g, rep, g, hd)
                      * blocks.astype(jnp.float32), axis=4)

    y = dense(out.reshape(b, s, h * hd).astype(x.dtype), p["wo"])
    return y, {"k": k, "v": v}


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
) -> Tuple[jax.Array, Dict]:
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
        out = _attend(cfg, q_full, k_full, v, kv_chunk, scale=1.0 / denom)
        k_rope_r = k_rope_r[:, :, 0, :]
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

    y = dense(out.reshape(b, s, h * dv).astype(x.dtype), p["wo"])
    # what a decode reads: the normed latent and the rotated key
    return y, {"c_kv": c_kv, "k_rope": k_rope_r}


# =============================================================================
# The attention kind and the serving cache
# =============================================================================
def attn_defs(cfg: AttnConfig) -> Dict[str, ParamDef]:
    return mla_defs(cfg) if cfg.kv_lora_rank else gqa_defs(cfg)


def attn_apply(
    p: Params,
    cfg: AttnConfig,
    x: jax.Array,
    positions: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Dict]:
    """(output, cache entries) of GQA or, with a ``kv_lora_rank``, MLA."""
    if cfg.kv_lora_rank:
        with jax.named_scope("mla"):
            return mla_apply(p, cfg, x, positions, cache, kv_chunk)
    return gqa_apply(p, cfg, x, positions, cache, kv_chunk)


def attn_init_cache(cfg: AttnConfig, batch: int, max_seq: int, dtype):
    """One layer's empty cache."""
    if cfg.kv_lora_rank:
        shapes = {"c_kv": (batch, max_seq, cfg.kv_lora_rank),
                  "k_rope": (batch, max_seq, cfg.qk_rope_dim)}
    else:
        g = kv_group(cfg)
        kv = (batch, cfg.n_kv_heads // g, max_seq, g * cfg.head_dim)
        shapes = {"k": kv, "v": kv}
    cache = {key: jnp.zeros(shape, dtype) for key, shape in shapes.items()}
    cache["pos"] = jnp.int32(0)
    return cache


def cache_from_prefill(entries: Dict, max_seq: int) -> Dict:
    """One layer's cache after a prefill: its entries (B, ..., S, W) padded
    on the sequence axis to ``max_seq``, and ``pos`` S."""
    s = next(iter(entries.values())).shape[-2]
    cache = {key: jnp.pad(a, ((0, 0),) * (a.ndim - 2)
                          + ((0, max_seq - s), (0, 0)))
             for key, a in entries.items()}
    cache["pos"] = jnp.int32(s)
    return cache


def write_token(stacked: Dict, entries: Dict) -> Dict:
    """The stacked cache (L, B, ..., S_max, W) with every layer's new-token
    entries (L, B, ..., 1, W) written in place at row ``pos``, and ``pos``
    + 1."""
    pos = stacked["pos"][0]
    cache = {key: jax.lax.dynamic_update_slice(
                 stacked[key], a, (0,) * (a.ndim - 2) + (pos, 0))
             for key, a in entries.items()}
    cache["pos"] = stacked["pos"] + 1
    return cache
