"""Random weights made by the benchmark, from the seed, on the device.

The program's parameter layout (the tree its ``model.init`` returns) is
read as shapes only; every value is drawn here, in one jitted call, so the
reference and the program read the same arrays and neither made them.
How each leaf is drawn is the architecture's weights plan
(``archs/<name>.py``: ``CONTRACT``, ``ONES``, ``EMBED``): matrices are
N(0, 1/fan_in) over the dims they contract, norms are ones, token tables
N(0, 0.02^2). Parameters are float32, the type the configurations serve
and train in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            return k
    return ""


def _stacked(path) -> bool:
    return any(getattr(p, "key", None) == "groups" for p in path)


def plan(flat, arch) -> list:
    """(kind, std) of each leaf of ``flat`` (``tree_flatten_with_path`` of
    the program's shapes), from ``arch``'s weights plan. A leaf the plan
    does not name is an error that names it."""
    out = []
    for path, sd in flat:
        name = _leaf_name(path)
        lead = 1 if _stacked(path) else 0
        if name in arch.ONES:
            out.append(("ones", None))
        elif name in arch.EMBED:
            out.append(("normal", 0.02))
        elif name in arch.CONTRACT:
            fan_in = math.prod(sd.shape[lead + a] for a in arch.CONTRACT[name])
            out.append(("normal", 1.0 / math.sqrt(fan_in)))
        else:
            raise ValueError(f"no initialiser for parameter {name!r} "
                             f"({jax.tree_util.keystr(path)}) in the "
                             f"weights plan of {arch.__name__}")
    return out


def make_params(cfg, seed: int, arch, shardings=None):
    """Parameters in the program's layout, drawn from ``seed`` by the
    weights plan of the architecture module ``arch``."""
    from repro.models import model as model_lib

    shapes = jax.eval_shape(lambda k: model_lib.init(cfg, k),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = plan(flat, arch)

    def build(key):
        leaves = []
        for i, ((_path, sd), (kind, std)) in enumerate(zip(flat, kinds)):
            if kind == "ones":
                leaves.append(jnp.ones(sd.shape, sd.dtype))
            else:
                k = jax.random.fold_in(key, i)
                leaves.append((jax.random.normal(k, sd.shape, jnp.float32)
                               * std).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed, 1))


def seed_key(seed: int, salt: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    k = jax.random.PRNGKey(salt)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
