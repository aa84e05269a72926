"""Decoder-only transformer LM: dense GQA, MLA, and MoE variants, with
optional vision/audio embedding prefix (VLM stub per assignment).

Layers are scanned (stacked params, ``jax.lax.scan``) to keep HLO size
O(1) in depth — essential for 512-device SPMD compiles.  Remat is applied
per-layer via ``jax.checkpoint`` with a configurable policy.  An MoE model
with ``first_k_dense`` leading dense layers has two stacks, scanned in turn:
``dense_blocks`` and then ``blocks``; its serving cache keeps the leading
stack's layers under the key ``dense_blocks``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.api import shard_act
from repro.models.attention import (AttnConfig, attn_apply, attn_defs,
                                    attn_init_cache, cache_from_prefill,
                                    write_token)
from repro.models.common import (ParamDef, Params, cross_entropy_from_hidden,
                                 dense, mlp_apply, mlp_defs, rms_norm,
                                 stack_defs)
from repro.models.config import ArchConfig
from repro.models.moe import (MoEConfig, moe_apply,
                              moe_apply_dropless, moe_defs)


def attn_config(cfg: ArchConfig) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.eff_head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_rope_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim,
        latent_norm=cfg.mla_latent_norm,
        yarn=cfg.rope_yarn,
    )


def moe_config(cfg: ArchConfig) -> MoEConfig:
    return MoEConfig(
        d_model=cfg.d_model,
        d_ff=cfg.moe_d_ff or cfg.d_ff,
        n_experts=cfg.n_experts,
        top_k=cfg.top_k,
        n_shared=cfg.n_shared_experts,
        norm_topk=cfg.moe_norm_topk,
        share=cfg.expert_share,
    )


LEAD = "dense_blocks"


def stacks(cfg: ArchConfig) -> Tuple[Tuple[str, int, bool], ...]:
    """(params key, layers, MoE?) of each scanned stack, in order."""
    moe = bool(cfg.n_experts)
    k = cfg.first_k_dense
    if not k:
        return (("blocks", cfg.n_layers, moe),)
    return ((LEAD, k, False), ("blocks", cfg.n_layers - k, moe))


# =============================================================================
# Parameter definitions
# =============================================================================
def block_defs(cfg: ArchConfig, moe: bool) -> Dict[str, Any]:
    defs: Dict[str, Any] = {
        "ln1": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "ln2": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn_defs(attn_config(cfg)),
    }
    if moe:
        defs["moe"] = moe_defs(moe_config(cfg))
    else:
        defs["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, gated=True)
    return defs


def lm_defs(cfg: ArchConfig) -> Dict[str, Any]:
    v = cfg.padded_vocab
    defs: Dict[str, Any] = {
        "embed": ParamDef((v, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "final_ln": ParamDef((cfg.d_model,), ("embed",), init="ones"),
    }
    for key, n, moe in stacks(cfg):
        defs[key] = stack_defs(block_defs(cfg, moe), n)
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, v), ("embed", "vocab"),
                                   scale=0.02)
    if cfg.frontend == "vision":
        defs["vis_proj"] = ParamDef((cfg.frontend_dim, cfg.d_model),
                                    (None, "embed"))
    return defs


# =============================================================================
# Forward
# =============================================================================
def _block_apply(cfg: ArchConfig, lp: Params, x: jax.Array,
                 kv_chunk: int, moe: bool) -> Tuple[jax.Array, jax.Array]:
    h, _ = attn_apply(lp["attn"], attn_config(cfg), rms_norm(x, lp["ln1"]),
                      kv_chunk=kv_chunk)
    x = x + h
    x = shard_act(x, ("batch", None, None))
    h = rms_norm(x, lp["ln2"])
    if moe:
        h, aux = moe_apply(lp["moe"], moe_config(cfg), h)
    else:
        h, aux = mlp_apply(lp["mlp"], h, cfg.activation), jnp.float32(0.0)
    x = x + h
    return shard_act(x, ("batch", None, None)), aux


def embed_inputs(cfg: ArchConfig, params: Params, batch: Dict) -> jax.Array:
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        vis = dense(batch["patch_embeds"].astype(x.dtype), params["vis_proj"])
        x = jnp.concatenate([vis, x], axis=1)
    return shard_act(x, ("batch", None, None))


def forward_hidden(
    cfg: ArchConfig, params: Params, batch: Dict,
    remat: str = "nothing_saveable", kv_chunk: int = 1024,
) -> Tuple[jax.Array, jax.Array]:
    """Token (+prefix) embeddings -> final hidden states, scanning layers."""
    x = embed_inputs(cfg, params, batch)
    aux = jnp.float32(0.0)
    for key, _, moe in stacks(cfg):
        def body(carry, lp, moe=moe):
            x, aux = carry
            x, a = _block_apply(cfg, lp, x, kv_chunk, moe)
            return (x, aux + a), None

        body_fn = body
        if remat != "none":
            policy = {
                "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
                "dots_saveable": jax.checkpoint_policies.dots_saveable,
                "dots_with_no_batch_dims": (
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable),
            }[remat]
            body_fn = jax.checkpoint(body, policy=policy)

        (x, aux), _ = jax.lax.scan(body_fn, (x, aux), params[key])
    return rms_norm(x, params["final_ln"]), aux


def lm_loss(
    cfg: ArchConfig, params: Params, batch: Dict,
    remat: str = "nothing_saveable", kv_chunk: int = 1024,
    loss_chunks: int = 1,
) -> jax.Array:
    hidden, aux = forward_hidden(cfg, params, batch, remat, kv_chunk)
    w_out = params.get("lm_head")
    if w_out is None:
        w_out = params["embed"].T
    labels = batch["labels"]
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        hidden = hidden[:, batch["patch_embeds"].shape[1]:]
    ce = cross_entropy_from_hidden(hidden, w_out, labels,
                                   seq_chunks=loss_chunks)
    return ce + aux


# =============================================================================
# Serving: prefill + decode
# =============================================================================
def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype) -> Dict:
    one = attn_init_cache(attn_config(cfg), batch, max_seq, dtype)

    # stack along layers for scan: every leaf gets a leading L axis
    def stacked(n):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)

    return _join_cache({key: stacked(n) for key, n, _ in stacks(cfg)})


def _split_cache(cache: Dict) -> Dict[str, Dict]:
    """The serving cache as one stacked cache per stack of ``stacks``."""
    out = {"blocks": {k: v for k, v in cache.items() if k != LEAD}}
    if LEAD in cache:
        out[LEAD] = cache[LEAD]
    return out


def _join_cache(per_stack: Dict[str, Dict]) -> Dict:
    cache = dict(per_stack["blocks"])
    if LEAD in per_stack:
        cache[LEAD] = per_stack[LEAD]
    return cache


def decode_step(
    cfg: ArchConfig, params: Params, cache: Dict, batch: Dict,
    moe_counts: bool = False,
) -> Tuple[jax.Array, ...]:
    """One-token decode: batch["tokens"]: (B, 1) -> (logits, new cache).

    Layers are scanned; each layer emits only the new token's cache
    entries, which ``write_token`` writes once after the scan.

    With ``moe_counts`` it returns (logits, new cache, counts) as well:
    int32 (routed pairs of held experts, held experts that received a
    pair, MoE layers that read their held experts in place), summed over
    the MoE layers.
    """
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    caches = _split_cache(cache)
    counts = jnp.zeros(3, jnp.int32)
    for key, _, moe in stacks(cfg):
        def body(x, scanned, moe=moe):
            lp, cache_l = scanned
            h, new_c = attn_apply(lp["attn"], attn_config(cfg),
                                  rms_norm(x, lp["ln1"]), cache=cache_l)
            x = x + h
            h = rms_norm(x, lp["ln2"])
            if moe:
                with jax.named_scope("moe"):
                    h, c = moe_apply_dropless(lp["moe"], moe_config(cfg), h)
                return x + h, (new_c, c)
            h = mlp_apply(lp["mlp"], h, cfg.activation)
            return x + h, new_c

        x, out = jax.lax.scan(body, x, (params[key], caches[key]))
        if moe:
            out, c = out
            counts = counts + jnp.sum(c, axis=0)
        caches[key] = write_token(caches[key], out)
    x = rms_norm(x, params["final_ln"])
    w_out = params.get("lm_head")
    if w_out is None:
        w_out = params["embed"].T
    logits = dense(x, w_out)
    if moe_counts:
        return logits, _join_cache(caches), counts
    return logits, _join_cache(caches)


def prefill(
    cfg: ArchConfig, params: Params, batch: Dict, max_seq: int,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Dict]:
    """Process the prompt, building the KV cache; returns last-pos logits.

    Each layer caches the entries its own attention returns.
    """
    acfg = attn_config(cfg)
    x = embed_inputs(cfg, params, batch)
    b, s, _ = x.shape
    caches = {}
    for key, _, moe in stacks(cfg):
        def body(x, lp, moe=moe):
            h, entries = attn_apply(lp["attn"], acfg, rms_norm(x, lp["ln1"]),
                                    kv_chunk=kv_chunk)
            cache_l = cache_from_prefill(entries, max_seq)
            x = x + h
            h = rms_norm(x, lp["ln2"])
            if moe:
                # §Perf: global argsort dispatch all-gathers the full token
                # set across the data axis — at large-T prefill the
                # grouped-capacity einsum dispatch keeps routing local to
                # each shard (the collective-bound fix for llama4-scout
                # prefill_32k); dropless stays for small T where exactness
                # is cheap
                with jax.named_scope("moe"):
                    if b * s > 65536:
                        h, _ = moe_apply(lp["moe"], moe_config(cfg), h)
                    else:
                        h, _ = moe_apply_dropless(lp["moe"], moe_config(cfg),
                                                  h)
            else:
                h = mlp_apply(lp["mlp"], h, cfg.activation)
            return x + h, cache_l

        x, caches[key] = jax.lax.scan(body, x, params[key])
    x = rms_norm(x[:, -1:], params["final_ln"])
    w_out = params.get("lm_head")
    if w_out is None:
        w_out = params["embed"].T
    return dense(x, w_out), _join_cache(caches)
