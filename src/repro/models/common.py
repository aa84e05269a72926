"""Model substrate: parameter definitions with logical sharding axes,
norms, linear layers, RoPE, MLP variants, and the chunked cross-entropy.

Params are nested dicts of arrays.  Every leaf is declared via ``ParamDef``
(shape + logical axes + initializer) so shapes and shardings can never drift
apart; ``init_params`` materializes arrays and ``logical_specs`` extracts the
logical-axis tree consumed by distributed/sharding.py.

Logical axes used across the zoo:
    "layers"  — scan-over-layers stacking dim (never sharded)
    "embed"   — d_model dim          (FSDP: sharded over the data axis)
    "heads"   — attention head-dim product (TP: sharded over model axis)
    "kv"      — kv head-dim product  (TP when divisible)
    "mlp"     — feed-forward hidden  (TP)
    "vocab"   — vocabulary           (TP)
    "expert"  — MoE expert dim       (EP over the model axis)
    None      — replicated
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    spec: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones
    scale: Optional[float] = None  # stddev; default fan-in

    def __post_init__(self):
        assert len(self.shape) == len(self.spec), (self.shape, self.spec)


def _tree_map_defs(fn: Callable, defs):
    if isinstance(defs, ParamDef):
        return fn(defs)
    return {k: _tree_map_defs(fn, v) for k, v in defs.items()}


def init_params(rng: jax.Array, defs, dtype=jnp.float32) -> Params:
    """Materialize arrays for a ParamDef tree (deterministic per-leaf keys)."""
    leaves = []

    def collect(d, path):
        if isinstance(d, ParamDef):
            leaves.append((path, d))
        else:
            for k in sorted(d):
                collect(d[k], path + (k,))

    collect(defs, ())
    keys = jax.random.split(rng, max(len(leaves), 1))

    out: Params = {}
    for (path, d), key in zip(leaves, keys):
        if d.init == "zeros":
            arr = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            arr = jnp.ones(d.shape, dtype)
        else:
            fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
            std = d.scale if d.scale is not None else 1.0 / math.sqrt(fan_in)
            arr = (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dtype)
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = arr
    return out


def logical_specs(defs):
    """ParamDef tree -> tree of logical-axis tuples (mirrors init_params)."""
    return _tree_map_defs(lambda d: d.spec, defs)


def abstract_params(defs, dtype=jnp.float32):
    """ParamDef tree -> ShapeDtypeStruct tree (dry-run, no allocation)."""
    return _tree_map_defs(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs
    )


def stack_defs(defs, n: int):
    """Prepend a scan-over-layers axis to every leaf."""
    return _tree_map_defs(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.spec, d.init,
                           d.scale),
        defs,
    )


# =============================================================================
# Elementary layers (pure functions over param dicts)
# =============================================================================
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gamma).astype(dt)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * gamma + beta).astype(dt)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b
    return y


# --- rotary position embeddings ----------------------------------------------
def rope_freqs(head_dim: int, max_pos: int, theta: float = 10000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    ang = jnp.outer(t, inv)          # (max_pos, head_dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling, as DeepSeek-V2's ``rope_scaling`` of type
    ``yarn`` states it: frequencies whose wavelength fits
    ``original_max_position`` more than ``beta_fast`` times keep their
    value, those fitting fewer than ``beta_slow`` times are divided by
    ``factor``, and the ones between are ramped linearly; the softmax scale
    is multiplied by ``softmax_factor``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """(dim/2,) float32 inverse frequencies of a rotary dimension."""
        def correction(rotations):
            return (dim * math.log(self.original_max_position
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction(self.beta_fast)), 0)
        high = min(math.ceil(correction(self.beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                       / (high - low), 0.0, 1.0)
        extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
        return (extra / self.factor * ramp + extra * (1.0 - ramp)
                ).astype(np.float32)

    @property
    def rotary_scale(self) -> float:
        """Factor on cos and sin (1 where ``mscale`` equals
        ``mscale_all_dim``)."""
        return (_yarn_mscale(self.factor, self.mscale)
                / _yarn_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_factor(self) -> float:
        return _yarn_mscale(self.factor, self.mscale_all_dim) ** 2


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0, yarn: Optional[YaRN] = None
               ) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S) int32.  Rotates interleaved
    pairs (x[2i], x[2i+1])."""
    d = x.shape[-1]
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv = jnp.asarray(yarn.inv_freq(d, theta))
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]   # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None and yarn.rotary_scale != 1.0:
        cos, sin = cos * yarn.rotary_scale, sin * yarn.rotary_scale
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# --- gated MLPs ----------------------------------------------------------------
def mlp_defs(d_model: int, d_ff: int, gated: bool = True) -> Dict[str, ParamDef]:
    defs = {
        "w_up": ParamDef((d_model, d_ff), ("embed", "mlp")),
        "w_down": ParamDef((d_ff, d_model), ("mlp", "embed")),
    }
    if gated:
        defs["w_gate"] = ParamDef((d_model, d_ff), ("embed", "mlp"))
    return defs


def mlp_apply(p: Params, x: jax.Array, activation: str = "silu") -> jax.Array:
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
           "relu": jax.nn.relu}[activation]
    up = dense(x, p["w_up"])
    if "w_gate" in p:
        up = act(dense(x, p["w_gate"])) * up
    else:
        up = act(up)
    return dense(up, p["w_down"])


# =============================================================================
# Loss: cross-entropy, optionally chunked along sequence to bound the
# (tokens, vocab) logits working set (beyond-paper memory optimization).
# =============================================================================
def cross_entropy_from_hidden(
    hidden: jax.Array,        # (B, S, D)
    w_out: jax.Array,         # (D, V)
    labels: jax.Array,        # (B, S) int32
    seq_chunks: int = 1,
) -> jax.Array:
    b, s, d = hidden.shape
    v = w_out.shape[-1]
    if seq_chunks <= 1:
        logits = jnp.einsum("bsd,dv->bsv", hidden, w_out).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    assert s % seq_chunks == 0, (s, seq_chunks)
    cs = s // seq_chunks
    h = hidden.reshape(b, seq_chunks, cs, d).swapaxes(0, 1)   # (C, B, cs, D)
    y = labels.reshape(b, seq_chunks, cs).swapaxes(0, 1)      # (C, B, cs)

    def chunk_loss(carry, hy):
        hc, yc = hy
        logits = jnp.einsum("bsd,dv->bsv", hc, w_out).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(chunk_loss, jnp.float32(0.0), (h, y))
    return total / (b * s)
