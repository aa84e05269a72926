"""Logical-axis → mesh-axis sharding rules (FSDP × TP × EP × SP).

Params carry logical axes from their ParamDefs; activations carry logical
axes at shard_act call sites.  Rules map logical names to mesh axes; a
dimension whose size does not divide the mesh-axis extent is silently
replicated (e.g. 8 KV heads on a 16-way model axis), which keeps every
architecture compilable under every mesh — the autotuner then *tunes* which
rules to enable (the paper's technique applied to distribution configs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-name -> mesh axis (or tuple of axes) mapping."""

    rules: Tuple[Tuple[str, Any], ...]

    def get(self, name: Optional[str]):
        if name is None:
            return None
        for k, v in self.rules:
            if k == name:
                return v
        return None

    def replace(self, **kw) -> "ShardingRules":
        d = dict(self.rules)
        d.update(kw)
        return ShardingRules(tuple(d.items()))


def default_rules(multi_pod: bool, fsdp: bool = True,
                  tp: bool = True) -> ShardingRules:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    model = "model" if tp else None
    return ShardingRules(tuple({
        "batch": batch_axes,
        "vocab": model,
        "heads": model,
        "kv": model,
        "mlp": model,
        "expert": model,
        "embed": "data" if fsdp else None,   # FSDP: shard params over data
        "seq": "data",                        # SP for long-context cells
        "layers": None,
    }.items()))


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def spec_for(
    mesh: Mesh, rules: ShardingRules,
    logical: Sequence[Optional[str]], shape: Sequence[int],
) -> P:
    """Build a PartitionSpec, dropping axes that do not divide evenly."""
    parts = []
    used: set = set()
    for name, dim in zip(logical, shape):
        axes = rules.get(name)
        if axes is None:
            parts.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        size = _axis_size(mesh, axes)
        if size <= 1 or dim % size != 0:
            parts.append(None)
            continue
        used.update(axes)
        parts.append(axes if len(axes) > 1 else axes[0])
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def param_shardings(mesh: Mesh, rules: ShardingRules, specs_tree,
                    abstract_tree):
    """Logical-spec tree + abstract-shape tree -> NamedSharding tree."""
    def one(spec, abstract):
        return NamedSharding(
            mesh, spec_for(mesh, rules, spec, abstract.shape))

    return jax.tree.map(
        one, specs_tree, abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def train_state_shardings(mesh: Mesh, rules: ShardingRules, specs_tree,
                          state_abstract):
    """``TrainState`` shardings: params and both Adam moments follow the
    params' logical specs; the step counters are replicated."""
    rep = NamedSharding(mesh, P())
    opt = state_abstract.opt
    return type(state_abstract)(
        params=param_shardings(mesh, rules, specs_tree,
                               state_abstract.params),
        opt=type(opt)(m=param_shardings(mesh, rules, specs_tree, opt.m),
                      v=param_shardings(mesh, rules, specs_tree, opt.v),
                      count=rep),
        step=rep)


def make_act_resolver(mesh: Mesh, rules: ShardingRules):
    """Resolver for distributed/api.activation_sharding."""
    def resolve(x, logical):
        if len(logical) != x.ndim:
            return x
        spec = spec_for(mesh, rules, logical, x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return resolve


def batch_shardings(mesh: Mesh, rules: ShardingRules, batch_abstract):
    """Input batches: leading dim is batch, everything else replicated.

    Exception: very long sequence dims (> 65536) are sequence-sharded (SP)
    when the batch dim cannot be (global_batch == 1 long-context cells).
    """
    def one(ab):
        shape = ab.shape
        if not shape:
            return NamedSharding(mesh, P())
        logical: list = [None] * len(shape)
        logical[0] = "batch"
        if shape[0] == 1 and len(shape) > 1 and shape[1] > 65536:
            logical[1] = "seq"
        return NamedSharding(mesh, spec_for(mesh, rules, logical, shape))

    return jax.tree.map(one, batch_abstract)


def cache_shardings(mesh: Mesh, rules: ShardingRules, cache_abstract,
                    global_batch: int, max_seq: int):
    """KV/SSM caches: shard the batch dim over data, the head-ish dim over
    model, and — when batch is unshardable (long-context, batch 1) — the
    sequence dim over data (SP).

    Dims are identified by SIZE (cache trees are heterogeneous across
    families): the first dim equal to ``global_batch`` is batch; the first
    later non-seq dim divisible by the model-axis extent is the TP dim.
    """
    model_extent = _axis_size(mesh, rules.get("heads"))

    def one(ab):
        shape = ab.shape
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        logical: list = [None] * len(shape)
        b_dim = None
        for i, d in enumerate(shape):
            if i >= 1 and d == global_batch:
                b_dim = i
                break
        if b_dim is not None:
            logical[b_dim] = "batch"
            for i in range(b_dim + 1, len(shape)):
                if shape[i] == max_seq:
                    if global_batch == 1 and max_seq > 65536:
                        logical[i] = "seq"
                    continue
                if model_extent > 1 and shape[i] % model_extent == 0:
                    logical[i] = "kv"
                    break
        return NamedSharding(mesh, spec_for(mesh, rules, logical, shape))

    return jax.tree.map(one, cache_abstract)
