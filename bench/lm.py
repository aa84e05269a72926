"""Dense decoder configurations: the program's model and seeded weights.

A configuration file holds the published ``config.json`` keys of a dense
decoder (Qwen2 / Llama style).  ``arch`` maps them onto the program's
``ArchConfig``.  ``make_weights`` makes the weights from ``--seed`` in one
jitted call on the device, in the dtype the file states (``torch_dtype``):

* ``layout="hf"``: the published layout and rotary convention (q and k
  rotated as two halves), the vocabulary at its published size.  The plain
  reference reads this one.
* ``layout="program"``: the same numbers in the program's parameter tree.
  The program rotates interleaved pairs, so the columns of q and k (and their
  biases) are permuted within each head, as a checkpoint converter does; the
  embedding is padded to the program's vocabulary with zero rows.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "layers": int(cfg["num_hidden_layers"]), "d": d, "heads": h,
        "kv_heads": int(cfg.get("num_key_value_heads", h)),
        "head_dim": int(cfg.get("head_dim", d // h)),
        "ff": int(cfg["intermediate_size"]), "vocab": int(cfg["vocab_size"]),
        "theta": float(cfg.get("rope_theta", 10000.0)),
        "eps": float(cfg.get("rms_norm_eps", 1e-6)),
        "bias": bool(cfg.get("qkv_bias", cfg.get("model_type") == "qwen2")),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def arch(name: str, cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.models.config import ArchConfig

    m = dims(cfg)
    if cfg.get("hidden_act", "silu") != "silu" or not m["tied"]:
        raise ValueError(f"{name}: only tied-embedding SiLU decoders are "
                         "mapped onto the program")
    return ArchConfig(
        name=name, family="dense", n_layers=m["layers"], d_model=m["d"],
        n_heads=m["heads"], n_kv_heads=m["kv_heads"], d_ff=m["ff"],
        vocab_size=m["vocab"], head_dim=m["head_dim"], qkv_bias=m["bias"],
        tie_embeddings=True, rope_theta=m["theta"],
        dtype=cfg.get("torch_dtype", "bfloat16"))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def _leaves(m: dict):
    """(name, shape, kind, fan_in) of every weight, in a fixed order."""
    L, d, ff = m["layers"], m["d"], m["ff"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    out = [("embed", (m["vocab"], d), "embed", 0),
           ("final_ln", (d,), "gain", 0),
           ("ln1", (L, d), "gain", 0), ("ln2", (L, d), "gain", 0),
           ("wq", (L, d, q), "matrix", d), ("wk", (L, d, kv), "matrix", d),
           ("wv", (L, d, kv), "matrix", d), ("wo", (L, q, d), "matrix", q),
           ("w_gate", (L, d, ff), "matrix", d),
           ("w_up", (L, d, ff), "matrix", d),
           ("w_down", (L, ff, d), "matrix", ff)]
    if m["bias"]:
        out += [("bq", (L, q), "bias", 0), ("bk", (L, kv), "bias", 0),
                ("bv", (L, kv), "bias", 0)]
    return out


def _rope_perm(m: dict, heads: int) -> np.ndarray:
    """Column order taking half-rotation q/k columns to interleaved pairs."""
    hd = m["head_dim"]
    one = np.stack([np.arange(hd // 2), hd // 2 + np.arange(hd // 2)],
                   axis=1).reshape(-1)
    return (np.arange(heads)[:, None] * hd + one[None, :]).reshape(-1)


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
                v = z * 0.02
            elif kind == "gain":
                v = 1.0 + 0.1 * z
            elif kind == "bias":
                v = 0.02 * z
            else:
                v = z / math.sqrt(fan_in)
            w[name] = v.astype(dtype)
        if layout == "hf":
            return w
        qp = _rope_perm(m, m["heads"])
        kp = _rope_perm(m, m["kv_heads"])
        attn = {"wq": w["wq"][..., qp], "wk": w["wk"][..., kp],
                "wv": w["wv"], "wo": w["wo"]}
        if m["bias"]:
            attn.update(bq=w["bq"][..., qp], bk=w["bk"][..., kp], bv=w["bv"])
        pad = max(0, padded_vocab - m["vocab"])
        return {
            "embed": jnp.pad(w["embed"], ((0, pad), (0, 0))),
            "final_ln": w["final_ln"],
            "blocks": {"ln1": w["ln1"], "ln2": w["ln2"], "attn": attn,
                       "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                               "w_down": w["w_down"]}},
        }

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


def param_count(cfg: dict) -> int:
    """Parameters at the published vocabulary (embedding counted once)."""
    return sum(int(np.prod(s)) for _, s, _, _ in _leaves(dims(cfg)))
