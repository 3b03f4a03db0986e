"""Fused sample-from-logits for the serving decode step (DESIGN.md §9).

The engine's greedy/temperature sampling used one `jax.random.categorical`
per slot under vmap. This module keeps the exact same sampling law but
restructures it Gumbel-max style so the per-slot decision is ONE masked
argmax — the shape a Pallas kernel wants (grid over slots, each program
reads its logit row once):

    categorical(k, lg / t)  ==  argmax(lg / t + gumbel(k, (V,)))

bitwise, because `jax.random.categorical` is defined as exactly that
argmax. The Gumbel noise is still drawn with the engine's per-slot key
chain ``fold_in(fold_in(fold_in(key, slot), tag), counter)`` — streams
are per-request and reproducible given the seed, and greedy rows
(temp <= 0) take a plain argmax, so token streams are bit-identical to
the pre-fusion engine (pinned by tests/test_paged_attn.py).

Audio (S, K, V) logits keep the legacy vmapped-categorical formulation —
multi-codebook rows are not on the paged serving path.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch

Array = jax.Array


def _fold3(key: Array, slot: Array, tag: Array, counter: Array) -> Array:
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(key, slot), tag), counter)


def argmax_low(x: Array, axis: int = -1) -> Array:
    """Argmax with EXPLICIT lowest-index tie-breaking.

    bf16 activations quantize logits onto a coarse grid, so exact argmax
    ties are common on real rows — and a compiled `jnp.argmax`'s tie
    winner is a property of the XLA reduction order, i.e. of the program
    it is fused into. Two compositions with bitwise-equal logits (the
    fused sampler vs its reference) can then emit different tokens. This
    spells the tie rule out — min index among the maxima — so every
    program agrees, and greedy parity pins survive bf16 (DESIGN.md §10).
    """
    m = jnp.max(x, axis=axis, keepdims=True)
    n = x.shape[axis]
    shape = [1] * x.ndim
    shape[axis] = n
    iota = jnp.arange(n, dtype=jnp.int32).reshape(shape)
    return jnp.min(jnp.where(x == m, iota, n), axis=axis).astype(jnp.int32)


def _argmax_kernel(x_ref, out_ref):
    """One grid program = one slot: argmax over its (1, V) row with the
    lowest-index tie-break of the jnp oracle's `argmax_low`."""
    x = x_ref[...]
    v = x.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    pick = jnp.min(jnp.where(x == jnp.max(x), iota, v))
    out_ref[...] = jnp.full(out_ref.shape, pick, jnp.int32)


@partial(jax.jit, static_argnames=("interpret",))
def _argmax_pallas(x: Array, *, interpret: bool) -> Array:
    s, v = x.shape
    out = pl.pallas_call(
        _argmax_kernel,
        grid=(s,),
        in_specs=[pl.BlockSpec((pl.Squeezed(), 1, v), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((pl.Squeezed(), 1, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, 1, 128), jnp.int32),
        interpret=interpret,
    )(x.reshape(s, 1, v))
    return out[:, 0, 0]


def sample_tokens(logits: Array, temps: Array, key: Array, tags: Array,
                  counters: Array, *, use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None) -> Array:
    """Greedy/temperature sampling for a decode batch on device.

    logits (S, V) or (S, K, V) float; temps (S,). Rows with temp <= 0
    take argmax; rows with temp > 0 sample categorically with the
    independent per-slot key chain (see module docstring). Returns (S,)
    (audio: (S, K)) int32.
    """
    d = dispatch.resolve(use_pallas, interpret)
    lg = logits.astype(jnp.float32)
    safe_t = jnp.maximum(temps, 1e-6)
    slots_iota = jnp.arange(logits.shape[0], dtype=jnp.int32)

    if logits.ndim == 3:  # audio (S, K, V): legacy formulation
        greedy = argmax_low(logits, axis=-1)

        def one(lgr, t, slot, tag, c):
            return jax.random.categorical(_fold3(key, slot, tag, c),
                                          lgr / t, axis=-1)

        sampled = jax.vmap(one)(lg, safe_t, slots_iota, tags,
                                counters).astype(jnp.int32)
        return jnp.where((temps > 0.0)[:, None], sampled, greedy)

    def noise_one(slot, tag, c):
        # gumbel(k, (V,), f32): the exact draw categorical(k, (V,)-logits)
        # makes internally, so the fused argmax reproduces it bitwise.
        return jax.random.gumbel(_fold3(key, slot, tag, c),
                                 (logits.shape[-1],), jnp.float32)

    noise = jax.vmap(noise_one)(slots_iota, tags, counters)
    # Tempered rows pick from the Gumbel-perturbed row, greedy rows from
    # the logits; the selection is elementwise XLA shared by both paths,
    # so the kernel and the reference argmax the same bits.
    row = jnp.where((temps > 0.0)[:, None], lg / safe_t[:, None] + noise, lg)
    if d.use_pallas:
        return _argmax_pallas(row, interpret=d.interpret)
    return argmax_low(row, axis=-1)
