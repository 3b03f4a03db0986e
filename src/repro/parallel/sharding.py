"""Logical-axis sharding: every parameter declares logical axis names
(models/common.ParamSpec); this module resolves them against a mesh.

Physical axes:
    "data"  — batch/FSDP axis (16 per pod)
    "model" — tensor/expert parallel axis (16 per pod)
    "pod"   — pod axis in the multi-pod mesh (DP or FSDP per pod_mode)

Default logical->physical rules (MaxText-style, FSDP on the embed dim):
    vocab/heads/kv_heads/ffw/experts/inner -> model   (TP / EP)
    embed                                  -> data(+pod)  (ZeRO-3/FSDP)
    batch                                  -> pod+data
    everything else                        -> replicated

Resolution is divisibility-aware with first-come-first-served conflict
handling: a dim whose mapped mesh axis is taken by an earlier dim (e.g. the
"ffw" dim of an expert weight whose "experts" dim already took "model") or
does not divide evenly falls back to replication — this is what makes e.g.
kv_heads=8 on model=16 (replicate KV, shard Q) work without per-arch
special cases.

`sharding_context` installs (mesh, rules) so model code can annotate
activations via `constrain` without threading mesh handles everywhere;
outside a context `constrain` is the identity (single-device tests).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffw": ("model",),
    "experts": ("model",),
    "inner": ("model",),
    "q_lora": (),
    "kv_lora": (),
    "state": (),
    "head_dim": (),
    "codebooks": (),
    "layers": (),
    "embed": ("data",),
    "batch": ("pod", "data"),
    "seq": (),
    "capacity": (),
    # cache-specific names (decode cells): the big KV buffers prefer the
    # model axis on kv_heads, falling back to head_dim when kv_heads does
    # not divide (GQA kv=8 on model=16), then staying replicated.
    "kv_seq": (),
    "head_dim_cache": ("model",),
    "kv_lora_cache": ("model",),
}


def make_rules(mesh: Mesh, *, pod_mode: str = "fsdp",
               overrides: Optional[Dict[str, Tuple[str, ...]]] = None
               ) -> Dict[str, Tuple[str, ...]]:
    """pod_mode: "fsdp" shards the embed (FSDP) dim over pod too; "dp" keeps
    pods as pure replicas (gradient all-reduce over pod — the compressed
    collective's target)."""
    rules = dict(DEFAULT_RULES)
    if "pod" in mesh.shape and pod_mode == "fsdp":
        rules["embed"] = ("pod", "data")
    if overrides:
        rules.update(overrides)
    return rules


def resolve_spec(shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
                 rules: Dict[str, Tuple[str, ...]], mesh: Mesh) -> P:
    used: set = set()
    parts = []
    for dim, name in zip(shape, axes):
        assigned: list = []
        if name is not None:
            for ax in rules.get(name, ()):
                if ax in used or ax not in mesh.shape:
                    continue
                factor = math.prod([mesh.shape[a] for a in assigned],
                                   start=mesh.shape[ax])
                if dim % factor == 0:
                    assigned.append(ax)
                    used.add(ax)
        if not assigned:
            parts.append(None)
        elif len(assigned) == 1:
            parts.append(assigned[0])
        else:
            parts.append(tuple(assigned))
    return P(*parts)


def tree_shardings(axes_tree: PyTree, shapes_tree: PyTree, mesh: Mesh,
                   rules: Dict[str, Tuple[str, ...]]) -> PyTree:
    """axes_tree leaves: tuples of logical names; shapes_tree: matching
    ShapeDtypeStruct/array leaves -> NamedSharding tree."""

    def leaf(axes, shaped):
        return NamedSharding(mesh, resolve_spec(tuple(shaped.shape), axes,
                                                rules, mesh))

    return jax.tree.map(leaf, axes_tree, shapes_tree,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and all(isinstance(a, (str, type(None))) for a in x))


# ---------------------------------------------------------------------------
# Context for activation constraints inside model code
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def sharding_context(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]]
                     = None):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (mesh, rules or make_rules(mesh))
    try:
        yield
    finally:
        _TLS.ctx = prev


def current_context():
    return getattr(_TLS, "ctx", None)


def constrain(x: jax.Array, axes: Tuple[Optional[str], ...]) -> jax.Array:
    """with_sharding_constraint by logical axes; identity w/o a context."""
    ctx = current_context()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = resolve_spec(tuple(x.shape), axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_shardings(batch_specs: PyTree, mesh: Mesh,
                    rules: Dict[str, Tuple[str, ...]]) -> PyTree:
    """Inputs: tokens/labels/mask (B, S[, K]) and patches (B, P, D): batch
    dim sharded, rest replicated."""

    def leaf(s):
        axes = ("batch",) + (None,) * (len(s.shape) - 1)
        return NamedSharding(mesh, resolve_spec(tuple(s.shape), axes, rules,
                                                mesh))

    return jax.tree.map(leaf, batch_specs)
