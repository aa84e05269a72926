"""Model configurations: the file, the seed, and the family that maps them.

A configuration file ``bench/configs/<name>.json`` holds the published
``config.json`` keys of a model and a ``reference`` key that names its
family.  The family's module ``bench/families/<reference>.py`` maps the file
onto the program and makes its weights from ``--seed`` (its docstring states
what such a module gives); its plain reference is
``bench/references/<reference>.py``.  This module reads no key of the file
but ``reference``, so a new model family enters with new files only.
"""
from __future__ import annotations

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def family(cfg: dict):
    """The module ``bench/families/<reference>.py`` of this configuration."""
    ref = cfg["reference"]
    name = f"bench.families.{ref}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"no model family {ref!r}: bench/families/{ref}.py "
                         "is missing") from None


def dims(cfg: dict) -> dict:
    return family(cfg).dims(cfg)


def arch(name: str, cfg: dict):
    return family(cfg).arch(name, cfg)


def make_weights(cfg: dict, seed: int, layout: str = "hf",
                 padded_vocab: int = 0):
    return family(cfg).make_weights(cfg, seed, layout, padded_vocab)


def param_count(cfg: dict) -> int:
    return family(cfg).param_count(cfg)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key
