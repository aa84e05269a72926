"""Mixture-of-Experts feed-forward with GShard-style grouped einsum dispatch.

Tokens are split into routing groups of ``group_size``; each group routes its
tokens into per-expert capacity buckets via a (G, Tg, E, C) dispatch one-hot.
Dispatch/combine einsums keep the all-to-all pattern visible to GSPMD, and the
dispatch tensor stays O(T · k · cf · Tg) — bounded by the group size, not the
global token count.  Expert tensors carry the "expert" logical axis (EP over
the mesh model axis).  Supports shared (always-on) experts (DeepSeek-V2) and
top-1 routing (Llama-4-Scout style).

Routing runs in float32 (the router's product included), so that rounding
of the model dtype rarely flips an expert choice.  ``norm_topk`` renormalises
the top-k gates to sum to one; without it they stay softmax probabilities
over all experts (DeepSeek-V2's ``norm_topk_prob: false``).  ``share`` =
(first, count) makes the layer an expert-parallel share: it holds the routed
experts first .. first + count - 1 only, routes over all ``n_experts``, and
returns its own experts' part of the result (plus the shared experts); the
pairs routed to experts held elsewhere are left out.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamDef, Params, mlp_apply, mlp_defs


class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int
    top_k: int
    n_shared: int = 0         # always-active shared experts
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001
    group_size: int = 256     # routing-group tokens (bounds dispatch tensor)
    norm_topk: bool = True    # renormalise the top-k gates to sum to one
    share: Tuple[int, ...] = ()   # (first, count) of experts held; () all


def held(cfg: MoEConfig) -> Tuple[int, int]:
    """(first, count) of the routed experts this layer holds."""
    return tuple(cfg.share) if cfg.share else (0, cfg.n_experts)


def moe_defs(cfg: MoEConfig) -> Dict[str, ParamDef]:
    d, f = cfg.d_model, cfg.d_ff
    e = held(cfg)[1]
    defs = {
        "router": ParamDef((d, cfg.n_experts), ("embed", None), scale=0.02),
        "w_gate": ParamDef((e, d, f), ("expert", "embed", "mlp")),
        "w_up": ParamDef((e, d, f), ("expert", "embed", "mlp")),
        "w_down": ParamDef((e, f, d), ("expert", "mlp", "embed")),
    }
    if cfg.n_shared:
        defs["shared"] = mlp_defs(d, f * cfg.n_shared, gated=True)
    return defs


def _route(p: Params, cfg: MoEConfig, x: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(softmax probabilities, top-k gates, top-k experts) of ``x``'s
    tokens, in float32."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def moe_apply(p: Params, cfg: MoEConfig, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    tg = min(cfg.group_size, t)
    assert t % tg == 0, (t, tg)
    g = t // tg
    xg = x.reshape(g, tg, d)

    probs, gate_vals, gate_idx = _route(p, cfg, xg)            # (G, Tg, k)

    # load-balancing auxiliary loss (Switch-style), over all tokens
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)

    capacity = int(cfg.capacity_factor * tg * k / e) + 1

    # bucket position of each (token, choice) within its expert, per group
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)      # (G, Tg, k, E)
    flat = onehot.reshape(g, tg * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                          # (G, Tg*k, E)
    pos = jnp.sum(pos * flat, axis=-1).reshape(g, tg, k)
    keep = pos < capacity

    # combine tensor: (G, Tg, E, C) = Σ_k gate · onehot(expert) ⊗ onehot(pos)
    # over the held experts (an expert held elsewhere has no one-hot column)
    first, n = held(cfg)
    combine = jnp.einsum(
        "gtke,gtkc->gtec",
        (gate_vals * keep).astype(x.dtype)[..., None]
        * jax.nn.one_hot(gate_idx - first, n, dtype=x.dtype),
        jax.nn.one_hot(pos, capacity, dtype=x.dtype),
    )
    dispatch = (combine > 0).astype(x.dtype)

    # expert inputs: (E, G, C, D)
    xin = jnp.einsum("gtec,gtd->egcd", dispatch, xg)
    h = jax.nn.silu(
        jnp.einsum("egcd,edf->egcf", xin, p["w_gate"])
    ) * jnp.einsum("egcd,edf->egcf", xin, p["w_up"])
    yout = jnp.einsum("egcf,efd->egcd", h, p["w_down"])        # (E, G, C, D)

    yg = jnp.einsum("gtec,egcd->gtd", combine, yout)

    out = yg.reshape(b, s, d)
    if cfg.n_shared:
        out = out + mlp_apply(p["shared"], x)
    return out, aux


def moe_apply_dropless(p: Params, cfg: MoEConfig, x: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Inference dispatch: exact, dropless, sorted-by-expert ragged matmuls.

    Serving paths must be prefill/decode consistent; capacity-bucket drops
    (acceptable statistical noise in training) would break that, so serving
    uses argsort dispatch + ``jax.lax.ragged_dot`` — the TPU-native grouped
    GEMM (vLLM/MegaBlocks-style dropless MoE).  The (token, expert) pairs of
    experts held elsewhere sort after every held expert's group, outside
    the groups, and add nothing.

    Returns (out, counts): counts is int32 (pairs routed to held experts,
    held experts that received at least one pair).
    """
    b, sq, d = x.shape
    t = b * sq
    k = cfg.top_k
    first, n = held(cfg)
    xt = x.reshape(t, d)

    _, gate_vals, gate_idx = _route(p, cfg, xt)              # (T, k)

    local = gate_idx.reshape(-1) - first                      # (T*k,)
    mine = (local >= 0) & (local < n)
    local = jnp.where(mine, local, n)
    order = jnp.argsort(local)
    tok_of = order // k                                       # source token
    xs = jnp.take(xt, tok_of, axis=0)                         # (T*k, D)
    group_sizes = jnp.bincount(local, length=n + 1)[:n]

    h = jax.nn.silu(
        jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    ) * jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    ys = jax.lax.ragged_dot(h, p["w_down"], group_sizes)      # (T*k, D)

    g = jnp.take(jnp.where(mine, gate_vals.reshape(-1), 0.0), order)
    out = jnp.zeros((t, d), ys.dtype).at[tok_of].add(
        ys * g[:, None].astype(ys.dtype))
    out = out.reshape(b, sq, d).astype(x.dtype)
    if cfg.n_shared:
        out = out + mlp_apply(p["shared"], x)
    counts = jnp.stack([jnp.sum(group_sizes),
                        jnp.sum(group_sizes > 0)]).astype(jnp.int32)
    return out, counts
