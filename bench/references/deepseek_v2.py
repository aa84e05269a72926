"""Plain float32 reference of DeepSeek-V2 (``DeepseekV2ForCausalLM``).

Written from the published architecture: RMSNorm; latent attention with the
query from one projection, ``kv_a_proj_with_mqa`` to the latent and a rotary
key shared by the heads, ``kv_a_layernorm`` on the latent and ``kv_b_proj``
from it to every head's key and value, full causal softmax attention scaled
by (qk_nope + qk_rope)^-1/2 times YaRN's mscale²; YaRN rotary frequencies
applied, as the published code does, after the interleaved pairs of q_pe and
k_pe are turned into halves; ``first_k_dense_replace`` dense SwiGLU layers,
then MoE layers: a softmax router in float32, greedy top-k, gates
renormalised only with ``norm_topk_prob``, the routed experts and the shared
experts (SwiGLU); an untied output head.  It reads the weights in the
published layout (``make_weights(layout="hf")``) and imports nothing of the
program.  Every matrix product runs at float32 ``highest`` precision, one
layer at a time.

The configuration is one chip's share of an expert-parallel deployment
(``dims``' ``held``): the router keeps its published width and the layer adds
only the held experts' part, each held expert computed on every token and
weighted by its gate (zero where the token did not pick it).

``quant`` computes the control, a step below the bfloat16 the configuration
serves in: every projection (attention, dense, experts, shared experts,
router and output head) multiplies quantised activations (one scale per
row) by quantised weights (one scale per output column), either symmetric
int8 (``"int8"``) or float8 e4m3 (``"fp8"``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _quantise(x, axis, quant):
    top = 127.0 if quant == "int8" else 448.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        return jnp.round(x / scale), scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def _mm(x, w, quant):
    if quant:
        xq, sx = _quantise(x, -1, quant)
        wq, sw = _quantise(w, 0, quant)
        return (xq @ wq) * sx * sw
    return x @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def yarn_get_mscale(scale, mscale):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(m: dict) -> np.ndarray:
    """The rotary inverse frequencies (qk_rope / 2,), YaRN's where the
    configuration scales them."""
    dim, base = m["rope"], m["theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if not m["yarn"]:
        return extra
    factor, orig, beta_fast, beta_slow, _, _ = m["yarn"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / dim))
    return (inter * (1 - inv_freq_mask) + extra * inv_freq_mask).astype(
        np.float32)


def softmax_scale(m: dict) -> float:
    scale = (m["nope"] + m["rope"]) ** -0.5
    if m["yarn"] and m["yarn"][5]:
        mscale = yarn_get_mscale(m["yarn"][0], m["yarn"][5])
        scale = scale * mscale * mscale
    return scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rope(x, m: dict):
    """(t, heads, qk_rope) at positions 0 .. t-1: the published
    ``apply_rotary_pos_emb``, interleaved pairs turned into halves first."""
    t, h, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq(m))
    emb = jnp.concatenate([ang, ang], -1)
    rot = 1.0
    if m["yarn"]:
        factor, _, _, _, mscale, mscale_all = m["yarn"]
        rot = (yarn_get_mscale(factor, mscale)
               / yarn_get_mscale(factor, mscale_all))
    cos = (jnp.cos(emb) * rot)[:, None, :]
    sin = (jnp.sin(emb) * rot)[:, None, :]
    x = x.reshape(t, h, d // 2, 2).swapaxes(-1, -2).reshape(t, h, d)
    return x * cos + _rotate_half(x) * sin


def _attention(x, lw, m, quant):
    t = x.shape[0]
    H, nope, dr, dv = m["heads"], m["nope"], m["rope"], m["v_head"]
    h = _rms(x, lw["input_layernorm"], m["eps"])
    q = _mm(h, lw["self_attn.q_proj"], quant).reshape(t, H, nope + dr)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = _mm(h, lw["self_attn.kv_a_proj_with_mqa"], quant)
    c, k_pe = ckv[:, :m["latent"]], ckv[:, m["latent"]:]
    kv = _mm(_rms(c, lw["self_attn.kv_a_layernorm"], m["eps"]),
             lw["self_attn.kv_b_proj"], quant).reshape(t, H, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rope(q_pe, m)
    k_pe = rope(k_pe[:, None, :], m)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, H, dr))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) * softmax_scale(m)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return x + _mm(o.reshape(t, H * dv), lw["self_attn.o_proj"], quant)


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def _moe(h, lw, m, quant):
    scores = jax.nn.softmax(_mm(h, lw["mlp.gate"], quant), axis=-1)
    weight, idx = jax.lax.top_k(scores, m["top_k"])
    if m["norm_topk"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    first, n = m["held"]
    y = _swiglu(h, lw["mlp.shared_experts.gate_proj"],
                lw["mlp.shared_experts.up_proj"],
                lw["mlp.shared_experts.down_proj"], quant)
    for j in range(n):
        g = jnp.sum(jnp.where(idx == first + j, weight, 0.0), -1)
        y = y + g[:, None] * _swiglu(
            h, lw[f"mlp.experts.{j}.gate_proj"],
            lw[f"mlp.experts.{j}.up_proj"],
            lw[f"mlp.experts.{j}.down_proj"], quant)
    return y


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, lw, m, quant):
    m = dict(m)
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    x = _attention(x, f32, m, quant)
    h = _rms(x, f32["post_attention_layernorm"], m["eps"])
    if "mlp.gate" in f32:
        return x + _moe(h, f32, m, quant)
    return x + _swiglu(h, f32["mlp.gate_proj"], f32["mlp.up_proj"],
                       f32["mlp.down_proj"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, norm, lm_head, m, quant):
    m = dict(m)
    h = _rms(x, norm.astype(jnp.float32), m["eps"])
    return _mm(h, lm_head.astype(jnp.float32), quant)


ATTENTION = ("input_layernorm", "post_attention_layernorm",
             "self_attn.q_proj", "self_attn.kv_a_proj_with_mqa",
             "self_attn.kv_a_layernorm", "self_attn.kv_b_proj",
             "self_attn.o_proj")


def layer_weights(m: dict, w: dict, i: int) -> dict:
    """The weights of layer ``i`` by published name (less the layer's
    prefix)."""
    lw = {k: w[k][i] for k in ATTENTION}
    if i < m["dense_layers"]:
        lw.update({k: w[k][i] for k in ("mlp.gate_proj", "mlp.up_proj",
                                        "mlp.down_proj")})
        return lw
    j = i - m["dense_layers"]
    lw.update({k: v[j] for k, v in w.items()
               if k == "mlp.gate" or k.startswith(("mlp.experts.",
                                                   "mlp.shared_experts."))})
    return lw


def logits(m: dict, w: dict, tokens: np.ndarray, rows: np.ndarray,
           quant: str = "", pad_to: int = 256) -> np.ndarray:
    """float32 logits (len(rows), vocab) of the causal LM over ``tokens`` at
    positions ``rows``.  The sequence is right-padded to a multiple of
    ``pad_to`` (causal attention leaves earlier positions unchanged) so that
    few shapes compile."""
    m = tuple(sorted(m.items()))
    md = dict(m)
    t = len(tokens)
    tp = -(-t // pad_to) * pad_to
    toks = np.zeros(tp, np.int32)
    toks[:t] = tokens
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed_tokens"], jnp.asarray(toks),
                     axis=0).astype(jnp.float32)
        for i in range(md["layers"]):
            x = _layer(x, layer_weights(md, w, i), m, quant)
        n = len(rows)
        npad = -(-n // 64) * 64
        idx = np.zeros(npad, np.int32)
        idx[:n] = rows
        out = _head(x[jnp.asarray(idx)], w["norm"], w["lm_head"], m, quant)
    return np.asarray(out[:n])
