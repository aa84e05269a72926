"""Plain float32 reference of a Qwen2 decoder (Hugging Face ``Qwen2ForCausalLM``).

Written from the published architecture: RMSNorm, q/k/v projections with bias,
rotary embeddings applied to two halves of each head, grouped-query causal
softmax attention, SwiGLU MLP, tied output embedding.  It reads the weights in
the published layout (``lm.make_weights(layout="hf")``) and imports nothing of
the program.  Every matrix product runs at float32 ``highest`` precision, one
layer at a time.

``quant`` computes the control, a step below the bfloat16 the configuration
serves in: every projection, the output head included, multiplies quantised
activations (one scale per row) by quantised weights (one scale per output
column), either symmetric int8 (``"int8"``) or float8 e4m3 (``"fp8"``).
"""
from __future__ import annotations

import functools

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


def _rope(x, theta):
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, lw, m, quant):
    m = dict(m)
    t = x.shape[0]
    H, Hk, hd = m["heads"], m["kv_heads"], m["head_dim"]
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    h = _rms(x, f32["ln1"], m["eps"])
    q = _mm(h, f32["wq"], quant) + f32.get("bq", 0.0)
    k = _mm(h, f32["wk"], quant) + f32.get("bk", 0.0)
    v = _mm(h, f32["wv"], quant) + f32.get("bv", 0.0)
    q = _rope(q.reshape(t, H, hd), m["theta"])
    k = jnp.repeat(_rope(k.reshape(t, Hk, hd), m["theta"]), H // Hk, axis=1)
    v = jnp.repeat(v.reshape(t, Hk, hd), H // Hk, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    x = x + _mm(o.reshape(t, H * hd), f32["wo"], quant)
    h = _rms(x, f32["ln2"], m["eps"])
    g = jax.nn.silu(_mm(h, f32["w_gate"], quant)) * _mm(h, f32["w_up"], quant)
    return x + _mm(g, f32["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, final_ln, embed, m, quant):
    m = dict(m)
    h = _rms(x, final_ln.astype(jnp.float32), m["eps"])
    return _mm(h, embed.astype(jnp.float32).T, quant)


PER_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
             "w_gate", "w_up", "w_down")


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
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
        for i in range(md["layers"]):
            lw = {k: w[k][i] for k in PER_LAYER if k in w}
            x = _layer(x, lw, m, quant)
        n = len(rows)
        npad = -(-n // 64) * 64
        idx = np.zeros(npad, np.int32)
        idx[:n] = rows
        out = _head(x[jnp.asarray(idx)], w["final_ln"], w["embed"], m, quant)
    return np.asarray(out[:n])
