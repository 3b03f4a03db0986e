"""Pallas TPU kernel for pass 2 of the two-pass block aligner
(core/timefloats.block_align, DESIGN.md §2).

The jnp form takes the block maxima from pass 1 and broadcasts them back
over each block. On a TPU that broadcast cannot stay in the operand's
layout when the blocks run along the lane axis: XLA transposes the whole
operand in HBM, materializes the per-element block scales and transposes
back. This kernel instead reads one tile of ``x`` with the per-tensor
prescale ``s`` (pass 1 reduces to the amax alone), rounds ``|x| s``, and
finds each block's max inside the tile, in VMEM:

- blocks along axis 0 (sublanes): a max over the (tm/B, B, tn) view of
  the tile, broadcast back;
- blocks along axis 1 (lanes): the same, on the tile transposed in VMEM,
  and the values transposed back.

By the monotonicity of E4M4 rounding the max of the rounded values is the
rounded block max, so every output element is bit-identical to the jnp
form. One read of ``x`` yields both layouts when both are asked for (the
cotangent's dx and dW operands). Compiled on a TPU; in interpret mode
elsewhere, where tests/test_block_align.py checks it against the jnp form.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.float8 import FloatFormat
from repro.core.timefloats import aligned_values, round_to_fmt

Array = jax.Array

LANES = 128


def _down_blocks(x: Array, s: Array, block: int, fmt: FloatFormat) -> Array:
    """Aligned values of a tile whose blocks run down its rows."""
    rows, cols = x.shape
    shape3 = (rows // block, block, cols)
    r3 = round_to_fmt(jnp.abs(x) * s, fmt).reshape(shape3)
    top = jnp.max(r3, axis=1, keepdims=True)
    return aligned_values(x.reshape(shape3), r3, top, fmt).reshape(rows, cols)


def _kernel(s_ref, x_ref, *out_refs, axes, block: int, fmt: FloatFormat):
    x = x_ref[...].astype(jnp.float32)
    tm, tn = x.shape
    for axis, o_ref in zip(axes, out_refs):
        if axis == 0:
            v = _down_blocks(x, s_ref[:1, :tn], block, fmt)
        else:
            v = _down_blocks(x.T, s_ref[:1, :tm], block, fmt).T
        o_ref[...] = v.astype(jnp.bfloat16)


@functools.partial(jax.jit,
                   static_argnames=("axes", "block", "fmt", "tm", "tn",
                                    "interpret"))
def block_align_pallas(x: Array, s: Array, *, axes: tuple, block: int,
                       fmt: FloatFormat, tm: int = 256, tn: int = 1024,
                       interpret: bool = False) -> tuple:
    """bf16 block-aligned values of ``x * s`` (2-D ``x``), one output per
    entry of ``axes`` (1: blocks along the last axis, 0: along the first).

    Rows are zero-padded here to a multiple of lcm(64, block) and columns
    to a multiple of lcm(128, block), and the padding is sliced off the
    outputs: whole zero blocks change no block's max, and the grid's edge
    tiles hold whole blocks of the padded array."""
    rmul, cmul = math.lcm(64, block), math.lcm(LANES, block)
    m, n = x.shape
    pm, pn = (-m) % rmul, (-n) % cmul
    xp = jnp.pad(x, ((0, pm), (0, pn))) if pm or pn else x
    mp, np_ = xp.shape
    tm = min(max(tm // rmul, 1) * rmul, mp)
    tn = min(max(tn // cmul, 1) * cmul, np_)
    sw = -(-max(tm, tn) // LANES) * LANES  # one row of the prescale
    spec = pl.BlockSpec((tm, tn), lambda i, j: (i, j))
    outs = pl.pallas_call(
        functools.partial(_kernel, axes=axes, block=block, fmt=fmt),
        grid=(pl.cdiv(mp, tm), pl.cdiv(np_, tn)),
        in_specs=[pl.BlockSpec((8, sw), lambda i, j: (0, 0)), spec],
        out_specs=[spec] * len(axes),
        out_shape=[jax.ShapeDtypeStruct((mp, np_), jnp.bfloat16)] * len(axes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.full((8, sw), s, jnp.float32), xp)
    return tuple(o[:m, :n] for o in outs)
