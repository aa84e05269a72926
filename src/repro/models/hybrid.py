"""Hybrid and SSM language models: Zamba2 (Mamba2 + shared attention) and
xLSTM (alternating mLSTM/sLSTM blocks).

Zamba2: the depth is organized into superblocks of ``mamba_per_attn`` Mamba2
layers followed by ONE shared transformer block (single parameter set reused
at every superblock — Zamba's signature parameter saving).  Superblocks are
scanned; the shared block rides along as a closure constant.

xLSTM: layers alternate mLSTM (chunkwise-parallel, linear attention-like)
and sLSTM (recurrent); pairs are scanned.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.api import shard_act
from repro.models.attention import (attn_apply, attn_defs, attn_init_cache,
                                    cache_from_prefill, write_token)
from repro.models.common import (ParamDef, Params, cross_entropy_from_hidden,
                                 dense, mlp_apply, mlp_defs, rms_norm,
                                 stack_defs)
from repro.models.config import ArchConfig
from repro.models.ssm import (Mamba2Config, mamba2_apply, mamba2_defs,
                              mamba2_init_cache)
from repro.models.transformer import attn_config
from repro.models.xlstm import (XLSTMConfig, mlstm_apply, mlstm_defs,
                                mlstm_init_cache, slstm_apply, slstm_defs,
                                slstm_init_cache)


# =============================================================================
# Zamba2
# =============================================================================
def mamba_config(cfg: ArchConfig) -> Mamba2Config:
    return Mamba2Config(d_model=cfg.d_model, d_state=cfg.ssm_state)


def zamba2_defs(cfg: ArchConfig) -> Dict[str, Any]:
    per = cfg.mamba_per_attn
    assert cfg.n_layers % per == 0
    n_super = cfg.n_layers // per
    mcfg = mamba_config(cfg)
    mamba_block = {
        "ln": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "mamba": mamba2_defs(mcfg),
    }
    shared_block = {
        "ln1": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "ln2": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn_defs(attn_config(cfg)),
        "mlp": mlp_defs(cfg.d_model, cfg.d_ff, gated=True),
    }
    v = cfg.padded_vocab
    return {
        "embed": ParamDef((v, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "supers": stack_defs(stack_defs(mamba_block, per), n_super),
        "shared": shared_block,
        "final_ln": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "lm_head": ParamDef((cfg.d_model, v), ("embed", "vocab"), scale=0.02),
    }


def _zamba_shared_apply(cfg: ArchConfig, sp: Params, x, cache=None):
    h, new_c = attn_apply(sp["attn"], attn_config(cfg),
                          rms_norm(x, sp["ln1"]), cache=cache)
    x = x + h
    x = x + mlp_apply(sp["mlp"], rms_norm(x, sp["ln2"]), cfg.activation)
    return x, new_c


def zamba2_loss(cfg: ArchConfig, params: Params, batch: Dict,
                remat: str = "nothing_saveable", loss_chunks: int = 1,
                **_) -> jax.Array:
    mcfg = mamba_config(cfg)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = shard_act(x, ("batch", None, None))

    def super_body(x, sb):
        def mamba_body(x, lp):
            h, _ = mamba2_apply(lp["mamba"], mcfg, rms_norm(x, lp["ln"]))
            return x + h, None

        inner = mamba_body if remat == "none" else jax.checkpoint(
            mamba_body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(inner, x, sb)
        x, _ = _zamba_shared_apply(cfg, params["shared"], x)
        return shard_act(x, ("batch", None, None)), None

    body = super_body if remat == "none" else jax.checkpoint(
        super_body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["supers"])
    hidden = rms_norm(x, params["final_ln"])
    return cross_entropy_from_hidden(hidden, params["lm_head"],
                                     batch["labels"], seq_chunks=loss_chunks)


def zamba2_init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype):
    per = cfg.mamba_per_attn
    n_super = cfg.n_layers // per
    mcfg = mamba_config(cfg)
    m1 = mamba2_init_cache(mcfg, batch, dtype)
    mcache = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_super, per) + a.shape).copy(), m1)
    a1 = attn_init_cache(attn_config(cfg), batch, max_seq, dtype)
    acache = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_super,) + a.shape).copy(), a1)
    return {"mamba": mcache, "attn": acache}


def zamba2_decode(cfg: ArchConfig, params: Params, cache: Dict, batch: Dict
                  ) -> Tuple[jax.Array, Dict]:
    mcfg = mamba_config(cfg)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)

    def super_body(x, scanned):
        sb, mcache_s, acache_s = scanned

        def mamba_body(x, inner):
            lp, mc = inner
            h, nc = mamba2_apply(lp["mamba"], mcfg, rms_norm(x, lp["ln"]),
                                 cache=mc)
            return x + h, nc

        x, new_m = jax.lax.scan(mamba_body, x, (sb, mcache_s))
        x, new_kv = _zamba_shared_apply(cfg, params["shared"], x,
                                        cache=acache_s)
        return x, (new_m, new_kv)

    x, (new_m, new_kv) = jax.lax.scan(
        super_body, x, (params["supers"], cache["mamba"], cache["attn"]))
    hidden = rms_norm(x, params["final_ln"])
    logits = dense(hidden, params["lm_head"])
    return logits, {"mamba": new_m, "attn": write_token(cache["attn"], new_kv)}


def zamba2_prefill(cfg: ArchConfig, params: Params, batch: Dict,
                   max_seq: int, **_) -> Tuple[jax.Array, Dict]:
    """Prefill via repeated decode is O(S²) — instead run the parallel form
    while accumulating caches per layer."""
    mcfg = mamba_config(cfg)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)

    def super_body(x, sb):
        def mamba_body(x, lp):
            h = rms_norm(x, lp["ln"])
            out, _ = mamba2_apply(lp["mamba"], mcfg, h)
            # final SSM state: run the chunked form once more on the last
            # position only is wrong; instead recompute state via decode-free
            # closed form — here we take the cheap route: rerun decode update
            # over the final conv window for the conv state and accept
            # recomputation of h via a single masked pass.
            mc = _mamba_state_from_prefix(lp["mamba"], mcfg, h)
            return x + out, mc

        x, mcaches = jax.lax.scan(mamba_body, x, sb)
        x, entries = _zamba_shared_apply(cfg, params["shared"], x)
        return x, (mcaches, cache_from_prefill(entries, max_seq))

    x, (mcache, acache) = jax.lax.scan(super_body, x, params["supers"])
    hidden = rms_norm(x[:, -1:], params["final_ln"])
    logits = dense(hidden, params["lm_head"])
    return logits, {"mamba": mcache, "attn": acache}


def _mamba_state_from_prefix(p: Params, mcfg: Mamba2Config, h: jax.Array):
    """Final (conv, ssm) state after consuming the whole prefix."""
    from repro.models.ssm import _causal_conv, _split_proj
    b, s, _ = h.shape
    di, n, hh, pd = (mcfg.d_inner, mcfg.d_state, mcfg.n_heads, mcfg.head_dim)
    z, xbc, dt = _split_proj(p, mcfg, h)
    conv_tail = xbc[:, s - (mcfg.conv_width - 1):, :]
    xbc_c = _causal_conv(p, mcfg, xbc)
    xs = xbc_c[..., :di].reshape(b, s, hh, pd)
    bm = xbc_c[..., di:di + n]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    ldec = dtv * a
    cum = jnp.cumsum(ldec, axis=1)
    w = jnp.exp(cum[:, -1:, :] - cum) * dtv
    hstate = jnp.einsum("bsh,bshp,bsn->bhpn", w, xs.astype(jnp.float32),
                        bm.astype(jnp.float32))
    return {"conv": conv_tail, "h": hstate, "pos": jnp.int32(s)}


# =============================================================================
# xLSTM LM
# =============================================================================
def xlstm_config(cfg: ArchConfig) -> XLSTMConfig:
    return XLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_heads)


def xlstm_defs(cfg: ArchConfig) -> Dict[str, Any]:
    assert cfg.n_layers % 2 == 0
    xcfg = xlstm_config(cfg)
    pair = {
        "ln_m": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "mlstm": mlstm_defs(xcfg),
        "ln_s": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "slstm": slstm_defs(xcfg),
    }
    v = cfg.padded_vocab
    return {
        "embed": ParamDef((v, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "pairs": stack_defs(pair, cfg.n_layers // 2),
        "final_ln": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "lm_head": ParamDef((cfg.d_model, v), ("embed", "vocab"), scale=0.02),
    }


def xlstm_loss(cfg: ArchConfig, params: Params, batch: Dict,
               remat: str = "nothing_saveable", loss_chunks: int = 1,
               **_) -> jax.Array:
    xcfg = xlstm_config(cfg)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = shard_act(x, ("batch", None, None))

    def body(x, lp):
        h, _ = mlstm_apply(lp["mlstm"], xcfg, rms_norm(x, lp["ln_m"]))
        x = x + h
        h, _ = slstm_apply(lp["slstm"], xcfg, rms_norm(x, lp["ln_s"]))
        return shard_act(x + h, ("batch", None, None)), None

    body_fn = body if remat == "none" else jax.checkpoint(
        body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body_fn, x, params["pairs"])
    hidden = rms_norm(x, params["final_ln"])
    return cross_entropy_from_hidden(hidden, params["lm_head"],
                                     batch["labels"], seq_chunks=loss_chunks)


def xlstm_init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype):
    xcfg = xlstm_config(cfg)
    n_pairs = cfg.n_layers // 2
    mc = mlstm_init_cache(xcfg, batch)
    sc = slstm_init_cache(xcfg, batch)
    stack = lambda tree: jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_pairs,) + a.shape).copy(), tree)
    return {"mlstm": stack(mc), "slstm": stack(sc)}


def xlstm_decode(cfg: ArchConfig, params: Params, cache: Dict, batch: Dict
                 ) -> Tuple[jax.Array, Dict]:
    xcfg = xlstm_config(cfg)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)

    def body(x, scanned):
        lp, mc, sc = scanned
        h, new_mc = mlstm_apply(lp["mlstm"], xcfg, rms_norm(x, lp["ln_m"]),
                                cache=mc)
        x = x + h
        h, new_sc = slstm_apply(lp["slstm"], xcfg, rms_norm(x, lp["ln_s"]),
                                cache=sc)
        return x + h, (new_mc, new_sc)

    x, (new_mc, new_sc) = jax.lax.scan(
        body, x, (params["pairs"], cache["mlstm"], cache["slstm"]))
    hidden = rms_norm(x, params["final_ln"])
    logits = dense(hidden, params["lm_head"])
    return logits, {"mlstm": new_mc, "slstm": new_sc}


def xlstm_prefill(cfg: ArchConfig, params: Params, batch: Dict,
                  max_seq: int, **_) -> Tuple[jax.Array, Dict]:
    """Run the parallel forms once per layer while extracting final states."""
    xcfg = xlstm_config(cfg)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)

    def body(x, lp):
        h_in = rms_norm(x, lp["ln_m"])
        h, _ = mlstm_apply(lp["mlstm"], xcfg, h_in)
        mc = _mlstm_state_from_prefix(lp["mlstm"], xcfg, h_in)
        x = x + h
        h_in = rms_norm(x, lp["ln_s"])
        h, sc = _slstm_full_with_state(lp["slstm"], xcfg, h_in)
        return x + h, (mc, sc)

    x, (mcache, scache) = jax.lax.scan(body, x, params["pairs"])
    hidden = rms_norm(x[:, -1:], params["final_ln"])
    logits = dense(hidden, params["lm_head"])
    return logits, {"mlstm": mcache, "slstm": scache}


def _mlstm_state_from_prefix(p: Params, xcfg: XLSTMConfig, x: jax.Array):
    from repro.models.xlstm import _CLIP
    b, s, _ = x.shape
    h, du = xcfg.n_heads, xcfg.d_up
    hd = du // h
    up = dense(x, p["w_up"])
    xb = up[..., :du]
    k = dense(xb, p["wk"]).reshape(b, s, h, hd).astype(jnp.float32) / (hd ** 0.5)
    v = dense(xb, p["wv"]).reshape(b, s, h, hd).astype(jnp.float32)
    gates = dense(xb, p["w_if"]) + p["b_if"]
    logi = jnp.clip(gates[..., :h].astype(jnp.float32), -_CLIP, _CLIP)
    logf = jax.nn.log_sigmoid(gates[..., h:].astype(jnp.float32))
    cum = jnp.cumsum(logf, axis=1)
    w = jnp.exp(jnp.clip(cum[:, -1:, :] - cum + logi, -_CLIP, _CLIP))
    c = jnp.einsum("bsh,bshd,bshe->bhde", w, v, k)
    n = jnp.einsum("bsh,bshd->bhd", w, k)
    return {"c": c, "n": n, "pos": jnp.int32(s)}


def _slstm_full_with_state(p: Params, xcfg: XLSTMConfig, x: jax.Array):
    from repro.models.xlstm import _CLIP, _slstm_step
    b, s, d = x.shape
    xp = dense(x, p["w_x"])
    zero = jnp.zeros((b, d), jnp.float32)
    carry0 = (zero, zero, zero, zero - _CLIP)

    def step(cr, xt):
        return _slstm_step(p, xcfg, cr, xt)

    carry, hs = jax.lax.scan(step, carry0, xp.swapaxes(0, 1))
    y = rms_norm(hs.swapaxes(0, 1).astype(x.dtype), p["norm_g"])
    sc = {"h": carry[0], "c": carry[1], "n": carry[2], "m": carry[3],
          "pos": jnp.int32(s)}
    return y, sc
