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


def route(p: Params, cfg: MoEConfig, x: jax.Array
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

    probs, gate_vals, gate_idx = route(p, cfg, xg)             # (G, Tg, k)

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


# Calls of at most this many tokens (T = B * S) take ``held_dense``, every
# token against every held expert; larger ones ``held_ragged``.  A dense
# product of T rows does 2 T FLOP per 2-byte bf16 weight, T FLOP a byte; a
# v5e's ridge is 197e12 / 819e9 = 240 FLOP a byte, so below it the dense
# product takes no longer than reading each held expert once.  The ragged
# path reads each held expert in full too, and in a layer scan first copies
# the layer's slice of the stacked weights: ``ragged_dot`` is a custom call
# and cannot read a slice in place, where a dot can.  128 keeps a factor of
# about two to the ridge: decode (T = batch) reads in place, prefills of
# more than 128 tokens keep the grouped product.
DENSE_MAX_TOKENS = 128


def _route_local(cfg: MoEConfig, gate_vals: jax.Array, gate_idx: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """(held expert index, gate) of each (token, choice), flattened; pairs
    routed to experts held elsewhere get index ``n`` and gate 0."""
    first, n = held(cfg)
    local = gate_idx.reshape(-1) - first
    mine = (local >= 0) & (local < n)
    return (jnp.where(mine, local, n),
            jnp.where(mine, gate_vals.reshape(-1), 0.0))


def held_ragged(p: Params, cfg: MoEConfig, xt: jax.Array,
                gate_vals: jax.Array, gate_idx: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of ``xt`` (T, D) by grouped products over the
    routed pairs, sorted by expert: (out (T, D), counts).  The pairs of
    experts held elsewhere sort after every held expert's group, outside
    the groups, and add nothing.  counts is int32 (pairs routed to held
    experts, held experts that received at least one pair)."""
    t, d = xt.shape
    n = held(cfg)[1]
    local, gates = _route_local(cfg, gate_vals, gate_idx)
    order = jnp.argsort(local)
    tok_of = order // cfg.top_k                               # source token
    xs = jnp.take(xt, tok_of, axis=0)                         # (T*k, D)
    group_sizes = jnp.bincount(local, length=n + 1)[:n]

    h = jax.nn.silu(
        jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    ) * jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    ys = jax.lax.ragged_dot(h, p["w_down"], group_sizes)      # (T*k, D)

    g = jnp.take(gates, order)
    out = jnp.zeros((t, d), ys.dtype).at[tok_of].add(
        ys * g[:, None].astype(ys.dtype))
    counts = jnp.stack([jnp.sum(group_sizes), jnp.sum(group_sizes > 0)])
    return out, counts.astype(jnp.int32)


def held_dense(p: Params, cfg: MoEConfig, xt: jax.Array,
               gate_vals: jax.Array, gate_idx: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """As ``held_ragged``, by plain dots of every token with every held
    expert, each (token, expert) scaled by the token's gate for that expert
    before the down product: exactly 0 where the token did not route to it.
    The dots read the weights where they lie, a layer's slice of stacked
    weights included."""
    t = xt.shape[0]
    n = held(cfg)[1]
    local, gates = _route_local(cfg, gate_vals, gate_idx)
    routed = local.reshape(t, cfg.top_k, 1) == jnp.arange(n)  # (T, k, n)
    g = jnp.sum(jnp.where(routed, gates.reshape(t, cfg.top_k, 1), 0.0),
                axis=1)                                       # (T, n)

    h = jax.nn.silu(
        jnp.einsum("td,edf->tef", xt, p["w_gate"])
    ) * jnp.einsum("td,edf->tef", xt, p["w_up"])              # (T, n, F)
    h = (h.astype(jnp.float32) * g[..., None]).astype(h.dtype)
    out = jnp.einsum("tef,efd->td", h, p["w_down"])
    counts = jnp.stack([jnp.sum(routed), jnp.sum(jnp.any(routed, (0, 1)))])
    return out, counts.astype(jnp.int32)


def moe_apply_dropless(p: Params, cfg: MoEConfig, x: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Inference dispatch: exact and dropless.

    Serving paths must be prefill/decode consistent; capacity-bucket drops
    (acceptable statistical noise in training) would break that.  Calls of
    up to ``DENSE_MAX_TOKENS`` tokens take ``held_dense``, larger ones
    ``held_ragged``: argsort dispatch + ``jax.lax.ragged_dot``, the
    TPU-native grouped GEMM (vLLM/MegaBlocks-style dropless MoE).

    Returns (out, counts): counts is int32 (pairs routed to held experts,
    held experts that received at least one pair, 1 where the held experts
    were read in place by ``held_dense`` else 0).
    """
    b, sq, d = x.shape
    t = b * sq
    xt = x.reshape(t, d)
    _, gate_vals, gate_idx = route(p, cfg, xt)               # (T, k)
    inplace = t <= DENSE_MAX_TOKENS
    out, counts = (held_dense if inplace else held_ragged)(
        p, cfg, xt, gate_vals, gate_idx)
    out = out.reshape(b, sq, d).astype(x.dtype)
    if cfg.n_shared:
        out = out + mlp_apply(p["shared"], x)
    return out, jnp.concatenate([counts, jnp.array([inplace], jnp.int32)])
