"""GPipe-style pipeline parallelism over a mesh axis via shard_map +
collective_permute, with full autodiff support (ppermute transposes to the
reverse permute, so jax.grad flows through the pipeline).

Schedule: classic GPipe fill-drain. With S stages and M microbatches the
loop runs T = M + S - 1 ticks; at tick t, stage s processes microbatch
t - s (when in range). Bubble fraction = (S-1)/T — reported by
`bubble_fraction` and verified in tests. Stage s holds layers
[s*L/S, (s+1)*L/S) as its shard of the layer-stacked params.

Used standalone (tests, examples) and by launch/dryrun.py's --pp mode for
homogeneous-stack (dense-family) models, mapping the "pod" axis to stages.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array
PyTree = Any


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(
    stage_fn: Callable[[PyTree, Array], Array],
    stage_params: PyTree,     # leaves (S, ...) — sharded over the stage axis
    x_micro: Array,           # (M, micro_batch, ...) — replicated input
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> Array:
    """Returns (M, micro_batch, ...) outputs of the last stage.

    Inside shard_map each device sees its stage's params (leading dim 1,
    squeezed) and runs the fill-drain loop; activations hop stages via
    ppermute. The final psum broadcasts last-stage outputs (a stage mask
    zeroes every other contribution), which keeps out_specs replicated —
    the caller computes the loss normally.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def run(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # (1, ...) -> (...)
        sid = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros((n_micro,) + xs.shape[1:] , xs.dtype)
        carry = jnp.zeros(xs.shape[1:], xs.dtype)

        def tick(t, state):
            carry, buf = state
            # stage 0 ingests microbatch t; others take the permuted carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(sid == 0, xs[mb_idx], carry)
            active = (t - sid >= 0) & (t - sid < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its output for microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            record = active & (sid == n_stages - 1)
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, jnp.where(record, y, buf[out_idx]), out_idx, 0)
            # hop to the next stage
            carry = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            return carry, buf

        carry, buf = jax.lax.fori_loop(0, ticks, tick, (carry, buf))
        # broadcast last stage's buffer to all stages (mask + psum)
        mask = (sid == n_stages - 1).astype(buf.dtype)
        return jax.lax.psum(buf * mask, axis)

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    return jax.shard_map(run, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(stage_params, x_micro)


def split_stages(stacked_params: PyTree, n_stages: int) -> PyTree:
    """(L, ...) layer-stacked params -> (S, L/S, ...) stage-major."""

    def f(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree.map(f, stacked_params)


def make_layer_stage_fn(layer_fn: Callable[[PyTree, Array], Array]):
    """Wrap a single-layer fn into a stage fn scanning its layer shard."""

    def stage_fn(params, x):
        def body(h, lp):
            return layer_fn(lp, h), None

        y, _ = jax.lax.scan(body, x, params)
        return y

    return stage_fn
