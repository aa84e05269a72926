"""Model registry: ArchConfig -> Model (init/loss/prefill/decode/input_specs).

``input_specs`` returns ShapeDtypeStruct stand-ins only (weak-type-correct,
shardable, no allocation) — the dry-run contract.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, hybrid, transformer
from repro.models.common import (abstract_params, init_params, logical_specs)
from repro.models.config import ArchConfig, ShapeConfig


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    defs: Any

    # --- parameters -----------------------------------------------------------
    def init(self, rng: jax.Array):
        return init_params(rng, self.defs, _dtype(self.cfg))

    def abstract(self):
        return abstract_params(self.defs, _dtype(self.cfg))

    def specs(self):
        return logical_specs(self.defs)

    # --- compute --------------------------------------------------------------
    def loss(self, params, batch, **opts) -> jax.Array:
        cfg = self.cfg
        if cfg.xlstm:
            return hybrid.xlstm_loss(cfg, params, batch, **opts)
        if cfg.mamba_per_attn:
            return hybrid.zamba2_loss(cfg, params, batch, **opts)
        if cfg.enc_layers:
            return encdec.encdec_loss(cfg, params, batch, **opts)
        return transformer.lm_loss(cfg, params, batch, **opts)

    def prefill(self, params, batch, max_seq: int, **opts):
        cfg = self.cfg
        if cfg.xlstm:
            return hybrid.xlstm_prefill(cfg, params, batch, max_seq, **opts)
        if cfg.mamba_per_attn:
            return hybrid.zamba2_prefill(cfg, params, batch, max_seq, **opts)
        if cfg.enc_layers:
            return encdec.encdec_prefill(cfg, params, batch, max_seq, **opts)
        return transformer.prefill(cfg, params, batch, max_seq, **opts)

    def decode(self, params, cache, batch, **opts):
        """(logits, new cache); an MoE transformer takes ``moe_counts``
        (``transformer.decode_step``)."""
        cfg = self.cfg
        if cfg.xlstm:
            return hybrid.xlstm_decode(cfg, params, cache, batch)
        if cfg.mamba_per_attn:
            return hybrid.zamba2_decode(cfg, params, cache, batch)
        if cfg.enc_layers:
            return encdec.encdec_decode(cfg, params, cache, batch)
        return transformer.decode_step(cfg, params, cache, batch, **opts)

    @property
    def counts_experts(self) -> bool:
        """Whether ``decode`` takes ``moe_counts``: a transformer with
        experts."""
        cfg = self.cfg
        return bool(cfg.n_experts) and not (cfg.xlstm or cfg.mamba_per_attn
                                            or cfg.enc_layers)

    def init_cache(self, batch: int, max_seq: int):
        cfg = self.cfg
        dt = _dtype(cfg)
        if cfg.xlstm:
            return hybrid.xlstm_init_cache(cfg, batch, max_seq, dt)
        if cfg.mamba_per_attn:
            return hybrid.zamba2_init_cache(cfg, batch, max_seq, dt)
        if cfg.enc_layers:
            return encdec.encdec_init_cache(cfg, batch, max_seq, dt)
        return transformer.init_cache(cfg, batch, max_seq, dt)

    def cache_specs(self, batch: int, max_seq: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_seq))

    # --- dry-run inputs ---------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this shape."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        f32 = jnp.float32
        if shape.kind == "train":
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            }
        elif shape.kind == "prefill":
            # modality prefixes count toward the sequence budget
            s_text = s - (cfg.frontend_len if cfg.frontend == "vision" else 0)
            specs = {"tokens": jax.ShapeDtypeStruct((b, s_text), i32)}
        else:  # decode: one new token against a cache of length s
            specs = {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
        if cfg.frontend == "vision" and shape.kind != "decode":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_len, cfg.frontend_dim), f32)
        if cfg.frontend == "audio" and shape.kind != "decode":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_len, cfg.frontend_dim), f32)
        return specs

    # --- bookkeeping --------------------------------------------------------------
    def param_count(self) -> int:
        total = 0

        def walk(d):
            nonlocal total
            if hasattr(d, "shape"):
                n = 1
                for x in d.shape:
                    n *= x
                total += n
            else:
                for v in d.values():
                    walk(v)

        walk(self.defs)
        return total

    def active_param_count(self) -> int:
        """MoE: params touched per token (shared + top_k of routed)."""
        cfg = self.cfg
        total = self.param_count()
        if not cfg.n_experts:
            return total
        f = cfg.moe_d_ff or cfg.d_ff
        per_expert = 3 * cfg.d_model * f
        routed_all = cfg.n_layers * cfg.n_experts * per_expert
        routed_active = cfg.n_layers * cfg.top_k * per_expert
        return total - routed_all + routed_active


def build_model(cfg: ArchConfig) -> Model:
    if cfg.xlstm:
        defs = hybrid.xlstm_defs(cfg)
    elif cfg.mamba_per_attn:
        defs = hybrid.zamba2_defs(cfg)
    elif cfg.enc_layers:
        defs = encdec.encdec_defs(cfg)
    else:
        defs = transformer.lm_defs(cfg)
    return Model(cfg=cfg, defs=defs)
