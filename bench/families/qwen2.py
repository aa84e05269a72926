"""The Qwen2 family: dense decoders (Qwen2 / Llama style) on the program.

The family module of every configuration file whose ``reference`` is
``qwen2``.  A family module (``bench/families/<reference>.py``) gives:

* ``dims(cfg)``: the sizes of the model as a dict with at least ``vocab``
  (the published vocabulary); the plain reference
  ``bench/references/<reference>.py`` reads the model from this dict, and
  the check reads ``vocab``;
* ``arch(name, cfg)``: the program's ``ArchConfig`` for the file;
* ``make_weights(cfg, seed, layout, padded_vocab=0)``: every weight from
  ``seed``, made on the device in one jitted call, in the dtype the file
  states (``torch_dtype``).  ``layout="hf"`` gives the published layout,
  which the plain reference reads; ``layout="program"`` the same numbers in
  the program's parameter tree, its vocabulary padded to ``padded_vocab``;
* ``param_count(cfg)``: the parameters at the published vocabulary.

Here ``layout="hf"`` keeps the published rotary convention (q and k rotated
as two halves).  The program rotates interleaved pairs, so in
``layout="program"`` the columns of q and k (and their biases) are permuted
within each head, as a checkpoint converter does, and the embedding is
padded with zero rows.  The operation and byte counts of this family are in
``bench/flops.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lm import DTYPES, seed_key


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
