"""The DeepSeek-V2 family: latent attention and sparse experts on the program.

The family module of every configuration file whose ``reference`` is
``deepseek_v2`` (the contract of a family module is in
``bench/families/qwen2.py``).  The published architecture
(``DeepseekV2ForCausalLM``): multi-head latent attention with the query in
one projection (``q_lora_rank`` null), ``kv_a_proj_with_mqa`` to the latent
and the shared rotary key, ``kv_a_layernorm`` on the latent and
``kv_b_proj`` from it to each head's key and value; YaRN rotary frequencies
with the mscale² softmax factor; ``first_k_dense_replace`` leading dense
SwiGLU layers, then MoE layers of softmax-scored greedy top-k experts, their
gates not renormalised (``norm_topk_prob`` false), beside always-on shared
experts; an untied output head.

The configuration holds a chip's share of an expert-parallel deployment:
``n_routed_experts`` experts of each MoE layer are held here, named by
``held_experts``, while the router keeps ``n_routed_experts_published``
outputs.  The program and the reference both leave out the absent experts'
part of every MoE layer.

``layout="hf"`` keeps the published tensors, each stacked over the layers
that have it and stored (in, out): ``self_attn.q_proj``,
``self_attn.kv_a_proj_with_mqa``, ``self_attn.kv_a_layernorm``,
``self_attn.kv_b_proj`` (per head [key | value] columns), ``self_attn.o_proj``,
the norms, ``mlp.{gate,up,down}_proj`` of the dense layers, ``mlp.gate`` (the
router), ``mlp.experts.{j}.{gate,up,down}_proj`` of each held expert j and
``mlp.shared_experts.{gate,up,down}_proj``.  ``layout="program"`` holds the
same numbers in the program's tree: ``kv_a_proj_with_mqa`` split into
``w_dkv`` and ``w_kr``, ``kv_b_proj`` into ``w_uk`` and ``w_uv``, and the held
experts stacked.  The published code rotates the rope part after turning its
interleaved pairs into halves; that equals the program's rotation of
interleaved pairs, so no column is permuted.

The least bytes of a decode call are counted here (``decode_bytes``,
``expert_bytes``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lm import DTYPES, seed_key

PUBLISHED_EPS = 1e-6        # the program's RMSNorm epsilon


def dims(cfg: dict) -> dict:
    held = [int(j) for j in cfg["held_experts"]]
    rs = cfg.get("rope_scaling")
    yarn = ()
    if rs:
        yarn = (float(rs["factor"]),
                int(rs["original_max_position_embeddings"]),
                float(rs["beta_fast"]), float(rs["beta_slow"]),
                float(rs["mscale"]), float(rs["mscale_all_dim"]))
    return {
        "layers": int(cfg["num_hidden_layers"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_head": int(cfg["v_head_dim"]), "latent": int(cfg["kv_lora_rank"]),
        "ff": int(cfg["intermediate_size"]),
        "expert_ff": int(cfg["moe_intermediate_size"]),
        "experts": int(cfg["n_routed_experts_published"]),
        "held": (held[0], len(held)) if held else (0, 0),
        "top_k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["n_shared_experts"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "vocab": int(cfg["vocab_size"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]), "yarn": yarn,
    }


def _check(name: str, cfg: dict, m: dict) -> None:
    """Refuse what the program does not compute."""
    want = {"hidden_act": "silu", "q_lora_rank": None,
            "topk_method": "greedy", "scoring_func": "softmax",
            "moe_layer_freq": 1, "attention_bias": False,
            "tie_word_embeddings": False, "routed_scaling_factor": 1}
    bad = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    held = [int(j) for j in cfg["held_experts"]]
    if held != list(range(m["held"][0], m["held"][0] + len(held))):
        bad["held_experts"] = held
    if len(held) != int(cfg["n_routed_experts"]):
        bad["n_routed_experts"] = cfg["n_routed_experts"]
    if not 0 <= m["held"][0] <= m["experts"] - m["held"][1]:
        bad["held_experts"] = held
    if cfg.get("num_key_value_heads", m["heads"]) != m["heads"]:
        bad["num_key_value_heads"] = cfg["num_key_value_heads"]
    if m["eps"] != PUBLISHED_EPS:
        bad["rms_norm_eps"] = m["eps"]
    rs = cfg.get("rope_scaling")
    if rs and rs.get("type") != "yarn":
        bad["rope_scaling"] = rs
    if bad:
        raise ValueError(f"{name}: the program does not map {bad}")


def arch(name: str, cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.models.common import YaRN
    from repro.models.config import ArchConfig

    m = dims(cfg)
    _check(name, cfg, m)
    return ArchConfig(
        name=name, family="moe", n_layers=m["layers"], d_model=m["d"],
        n_heads=m["heads"], n_kv_heads=m["heads"], d_ff=m["ff"],
        vocab_size=m["vocab"], head_dim=m["nope"], tie_embeddings=False,
        rope_theta=m["theta"], n_experts=m["experts"], top_k=m["top_k"],
        n_shared_experts=m["shared"], moe_d_ff=m["expert_ff"],
        first_k_dense=m["dense_layers"], moe_norm_topk=m["norm_topk"],
        expert_share=m["held"], kv_lora_rank=m["latent"],
        qk_rope_dim=m["rope"], v_head_dim=m["v_head"], mla_latent_norm=True,
        rope_yarn=YaRN(*m["yarn"]) if m["yarn"] else None,
        dtype=cfg.get("torch_dtype", "bfloat16"))


def _leaves(m: dict):
    """(name, shape, kind, fan_in) of every weight, in a fixed order; an
    attention or norm weight of stack ``dense`` or ``moe`` is stacked over
    that stack's layers."""
    d, h, v = m["d"], m["heads"], m["vocab"]
    r, dr = m["latent"], m["rope"]
    f, n, s = m["expert_ff"], m["held"][1], m["shared"] * m["expert_ff"]
    out = [("embed", (v, d), "embed", 0), ("final_ln", (d,), "gain", 0),
           ("lm_head", (d, v), "matrix", d)]
    for stack, L in (("dense", m["dense_layers"]),
                     ("moe", m["layers"] - m["dense_layers"])):
        out += [(f"{stack}.ln1", (L, d), "gain", 0),
                (f"{stack}.ln2", (L, d), "gain", 0),
                (f"{stack}.q_proj", (L, d, h * (m["nope"] + dr)), "matrix", d),
                (f"{stack}.kv_a", (L, d, r + dr), "matrix", d),
                (f"{stack}.kv_ln", (L, r), "gain", 0),
                (f"{stack}.kv_b", (L, r, h * (m["nope"] + m["v_head"])),
                 "matrix", r),
                (f"{stack}.o_proj", (L, h * m["v_head"], d), "matrix",
                 h * m["v_head"])]
    L, Lm = m["dense_layers"], m["layers"] - m["dense_layers"]
    out += [("dense.gate_proj", (L, d, m["ff"]), "matrix", d),
            ("dense.up_proj", (L, d, m["ff"]), "matrix", d),
            ("dense.down_proj", (L, m["ff"], d), "matrix", m["ff"]),
            ("moe.router", (Lm, d, m["experts"]), "matrix", d),
            ("moe.gate_proj", (Lm, n, d, f), "matrix", d),
            ("moe.up_proj", (Lm, n, d, f), "matrix", d),
            ("moe.down_proj", (Lm, n, f, d), "matrix", f),
            ("moe.shared.gate_proj", (Lm, d, s), "matrix", d),
            ("moe.shared.up_proj", (Lm, d, s), "matrix", d),
            ("moe.shared.down_proj", (Lm, s, d), "matrix", s)]
    return out


ATTN = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
        "q_proj": "self_attn.q_proj", "kv_a": "self_attn.kv_a_proj_with_mqa",
        "kv_ln": "self_attn.kv_a_layernorm", "kv_b": "self_attn.kv_b_proj",
        "o_proj": "self_attn.o_proj"}
PROJ = ("gate_proj", "up_proj", "down_proj")


def _hf(m: dict, w: dict) -> dict:
    out = {"embed_tokens": w["embed"], "norm": w["final_ln"],
           "lm_head": w["lm_head"]}
    for k, name in ATTN.items():
        out[name] = jnp.concatenate([w[f"dense.{k}"], w[f"moe.{k}"]])
    out["mlp.gate"] = w["moe.router"]
    for p in PROJ:
        out[f"mlp.{p}"] = w[f"dense.{p}"]
        out[f"mlp.shared_experts.{p}"] = w[f"moe.shared.{p}"]
        for j in range(m["held"][1]):
            out[f"mlp.experts.{j}.{p}"] = w[f"moe.{p}"][:, j]
    return out


def _program(m: dict, w: dict, padded_vocab: int) -> dict:
    h, r = m["heads"], m["latent"]
    pad = max(0, padded_vocab - m["vocab"])

    def block(stack, mlp):
        kv_b = w[f"{stack}.kv_b"]
        kv_b = kv_b.reshape(kv_b.shape[:2] + (h, m["nope"] + m["v_head"]))
        return {
            "ln1": w[f"{stack}.ln1"], "ln2": w[f"{stack}.ln2"],
            "attn": {
                "wq": w[f"{stack}.q_proj"],
                "w_dkv": w[f"{stack}.kv_a"][..., :r],
                "w_kr": w[f"{stack}.kv_a"][..., r:],
                "kv_ln": w[f"{stack}.kv_ln"],
                "w_uk": kv_b[..., :m["nope"]].reshape(
                    kv_b.shape[:2] + (h * m["nope"],)),
                "w_uv": kv_b[..., m["nope"]:].reshape(
                    kv_b.shape[:2] + (h * m["v_head"],)),
                "wo": w[f"{stack}.o_proj"]},
            **mlp}

    dense = {"mlp": {"w_gate": w["dense.gate_proj"],
                     "w_up": w["dense.up_proj"],
                     "w_down": w["dense.down_proj"]}}
    moe = {"moe": {"router": w["moe.router"],
                   "w_gate": w["moe.gate_proj"], "w_up": w["moe.up_proj"],
                   "w_down": w["moe.down_proj"],
                   "shared": {"w_gate": w["moe.shared.gate_proj"],
                              "w_up": w["moe.shared.up_proj"],
                              "w_down": w["moe.shared.down_proj"]}}}
    return {"embed": jnp.pad(w["embed"], ((0, pad), (0, 0))),
            "final_ln": w["final_ln"],
            "lm_head": jnp.pad(w["lm_head"], ((0, 0), (0, pad))),
            "dense_blocks": block("dense", dense),
            "blocks": block("moe", moe)}


def make_weights(cfg: dict, seed: int, layout: str = "hf",
                 padded_vocab: int = 0):
    """All weights of ``cfg`` from ``seed``, made on the device in one call."""
    m = dims(cfg)
    dtype = DTYPES[cfg.get("torch_dtype", "bfloat16")]
    leaves = _leaves(m)

    def build(key):
        w = {}
        for i, (name, shape, kind, fan_in) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if kind == "embed":
                val = z * 0.02
            elif kind == "gain":
                val = 1.0 + 0.1 * z
            else:
                val = z / math.sqrt(fan_in)
            w[name] = val.astype(dtype)
        return _hf(m, w) if layout == "hf" else _program(m, w, padded_vocab)

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


def _params(m: dict) -> int:
    return sum(int(np.prod(s)) for _, s, _, _ in _leaves(m))


def param_count(cfg: dict) -> int:
    """Parameters held here at the published vocabulary: the held experts
    only, the output head counted apart from the embedding."""
    return _params(dims(cfg))


# -- least bytes of a decode call ---------------------------------------------
def cache_bytes_per_position(m: dict, bytes_per_value: int = 2) -> int:
    """The latent and the rotary key of one position, all layers."""
    return m["layers"] * (m["latent"] + m["rope"]) * bytes_per_value


def expert_bytes(m: dict, bytes_per_value: int = 2) -> int:
    """One routed expert's weights."""
    return 3 * m["d"] * m["expert_ff"] * bytes_per_value


def decode_bytes(m: dict, positions, bytes_per_value: int = 2) -> float:
    """Least HBM traffic of one decode call over rows at ``positions``,
    its routed experts left out (``expert_bytes`` for each held expert a
    call touched): every other weight read once, of the embedding only the
    rows' own rows, the cache of the positions in use read and one position
    written per row."""
    routed = sum(int(np.prod(s)) for name, s, _, _ in _leaves(m)
                 if name in ("moe.gate_proj", "moe.up_proj", "moe.down_proj"))
    table = m["vocab"] * m["d"]
    rest = (_params(m) - routed - table) * bytes_per_value
    rows = len(positions) * m["d"] * bytes_per_value
    kv = cache_bytes_per_position(m, bytes_per_value)
    return float(rest + rows + sum(kv * (p + 1) for p in positions))
