"""Paged-KV page-table gather: ``out[b, t] = pool[page_table[b, t]]``.

The paged serving cache (DESIGN.md §8) stores K/V in fixed-size pages —
``pool (P, page, *feat)`` — and each batch row owns a page table
``pt (B, T)`` of page ids. The attention read path materializes the
per-row dense view ``(B, T, page, *feat)`` with this gather; on TPU that
is a DMA-friendly block copy, so it gets a Pallas kernel (one grid cell
per page-table entry; the scalar-prefetched table picks the page the
pipeline DMAs from the pool in HBM). The jnp reference is plain advanced
indexing, which XLA lowers to a gather; tests/test_paged.py checks the
kernel against it in interpret mode.

kernels/dispatch picks the path from the backend: the kernel, compiled,
on a TPU; the reference elsewhere. ``use_pallas=`` overrides per call.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

Array = jax.Array


def gather_pages_ref(pool: Array, page_table: Array) -> Array:
    """Reference/fallback: ``pool[pt]`` -> (B, T, page, *feat)."""
    return pool[page_table]


def _kernel(pt_ref, page_ref, out_ref):
    """One grid cell = one page-table entry: the index map has already
    DMA'd the referenced page; copy it out."""
    del pt_ref  # consumed by the index map
    out_ref[...] = page_ref[...]


@partial(jax.jit, static_argnames=("interpret",))
def gather_pages_pallas(pool: Array, page_table: Array,
                        *, interpret: bool | None = None) -> Array:
    """Pallas page gather; same contract as :func:`gather_pages_ref`.

    The page table is scalar-prefetched into SMEM and the pool stays in
    HBM: each grid step's block index names one page, so VMEM holds a
    page or two whatever the pool size."""
    if interpret is None:
        interpret = dispatch.current().interpret
    p, page = pool.shape[:2]
    feat = pool.shape[2:]
    f = math.prod(feat)
    b, t = page_table.shape
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t),
            in_specs=[pl.BlockSpec((pl.Squeezed(), page, f),
                                   lambda i, j, pt: (pt[i * t + j], 0, 0))],
            out_specs=pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), page, f),
                                   lambda i, j, pt: (i, j, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((b, t, page, f), pool.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32).reshape(-1), pool.reshape(p, page, f))
    return out.reshape((b, t, page) + feat)


def gather_pages(pool: Array, page_table: Array,
                 *, use_pallas: bool | None = None) -> Array:
    """Dispatch: the backend's choice (kernels/dispatch) unless
    ``use_pallas`` says otherwise."""
    d = dispatch.resolve(use_pallas)
    if d.use_pallas:
        return gather_pages_pallas(pool, page_table, interpret=d.interpret)
    return gather_pages_ref(pool, page_table)
