"""Jit'd public wrappers for the TimeFloats matmul kernel.

`timefloats_matmul(x, w, cfg)` is the drop-in used by
core.timefloats.matmul(mode="pallas"): it quantizes operands (XLA ops — the
elementwise field extraction fuses well and is not the hot spot), pads to
tile multiples, and invokes the Pallas kernel: compiled on a TPU, in
interpret mode elsewhere (kernels/dispatch), unless ``interpret`` says
otherwise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.timefloats import (
    DEFAULT,
    QuantizedOperand,
    TFConfig,
    dequantize_input,
    matmul_separable_transposed,
    quantize_input,
    quantize_weight,
)
from repro.kernels import dispatch
from repro.kernels import timefloats_matmul as kernel_mod

Array = jax.Array


def _pad_to(a: Array, mults: tuple[int, ...], pad_value=0) -> Array:
    widths = [(0, (-s) % m) for s, m in zip(a.shape, mults)]
    if all(w == (0, 0) for w in widths):
        return a
    return jnp.pad(a, widths, constant_values=pad_value)


def _rnd8(v: int) -> int:
    """Round tile dims up to a multiple of 8: sub-8 tiles are below any
    TPU register tile, and the Pallas interpreter has miscompiled some
    degenerate (m<=3, odd-n) tile shapes when the pallas_call is jitted
    with traced operands (bisected in tests/test_kernels.py — shapes like
    (2,1,9) returned a zero row)."""
    return -(-v // 8) * 8


def _tile_sizes(m: int, n: int, c: int, bm: int, bn: int, bc: int):
    """Shrink default tiles for small problems (tests sweep tiny shapes)
    but keep M/N tiles multiples of 8 (see _rnd8)."""
    return (min(bm, _rnd8(m)), min(bn, _rnd8(n)), min(bc, max(c, 1)))


@partial(jax.jit, static_argnames=("cfg", "bm", "bn", "bc", "interpret"))
def timefloats_matmul(
    x: Array,
    w: Array,
    cfg: TFConfig = DEFAULT,
    *,
    bm: int = 256,
    bn: int = 256,
    bc: int = 8,
    interpret: bool | None = None,
) -> Array:
    """f32/bf16 (M,K) @ (K,N) through the TimeFloats Pallas kernel."""
    if interpret is None:
        interpret = dispatch.current().interpret
    m_dim, n_dim = x.shape[0], w.shape[1]
    qx = quantize_input(x, cfg)
    qw = quantize_weight(w, cfg)
    y = quantized_matmul(qx, qw, cfg=cfg, bm=bm, bn=bn, bc=bc,
                         interpret=interpret)
    return y[:m_dim, :n_dim]


def quantized_matmul(
    qx: QuantizedOperand,
    qw: QuantizedOperand,
    *,
    cfg: TFConfig = DEFAULT,
    bm: int = 256,
    bn: int = 256,
    bc: int = 8,
    interpret: bool | None = None,
) -> Array:
    """Kernel invocation on pre-quantized operands; returns padded (M',N')."""
    if interpret is None:
        interpret = dispatch.current().interpret
    c, m_dim, blk = qx.q.shape
    n_dim = qw.q.shape[2]
    bm, bn, bc = _tile_sizes(m_dim, n_dim, c, bm, bn, bc)
    # Pad: zero q-blocks contribute nothing regardless of scale (scale=1 pad).
    qxq = _pad_to(qx.q, (bc, bm, blk))
    qxs = _pad_to(qx.scale, (bc, bm), pad_value=1.0)
    qwq = _pad_to(qw.q, (bc, blk, bn))
    qws = _pad_to(qw.scale, (bc, bn), pad_value=1.0)
    return kernel_mod.timefloats_matmul_quantized(
        qxq, qxs, qwq, qws, cfg=cfg, bm=bm, bn=bn, bc=bc, interpret=interpret)


@partial(jax.jit,
         static_argnames=("k_dim", "cfg", "bm", "bc", "tn", "interpret"))
def timefloats_matmul_transposed(
    g: Array,
    qw: QuantizedOperand,
    *,
    k_dim: int,
    cfg: TFConfig = DEFAULT,
    bm: int = 256,
    bc: int = 8,
    tn: int = 512,
    interpret: bool | None = None,
) -> Array:
    """dx = g @ W^T (M,N)x(K,N planes) through the transposed-read kernel.

    ``qw`` is the *stored* weight in the exact layout the forward kernel
    consumed — no re-quantization, no materialized W^T (DESIGN.md §3). The
    streamed gradient is quantized here, along its own contraction dim N,
    and handed to the kernel as its exact bf16 values.
    With an ADC configured the call falls back to the XLA reference
    (transposed reads are modeled ADC-free, so the numbers are identical;
    the kernel itself rejects adc_bits).
    """
    if interpret is None:
        interpret = dispatch.current().interpret
    if cfg.adc_bits is not None:
        return matmul_separable_transposed(g, qw, k_dim, cfg)
    m_dim, n_dim = g.shape
    gd = dequantize_input(quantize_input(g, cfg), n_dim)  # (M, N) bf16
    c_chunks = qw.q.shape[0]
    bm = min(bm, _rnd8(m_dim))
    bc = min(bc, c_chunks)
    tn = min(tn, -(-n_dim // 128) * 128)
    # Zero gradient columns meet zero weight columns (scale-1 pad), and
    # whole zero planes pad C: nothing padded contributes.
    gd = _pad_to(gd, (bm, tn))
    n_pad = gd.shape[1]
    qwq = _pad_to(qw.q, (bc, 1, n_pad))
    qws = _pad_to(qw.scale, (bc, n_pad), pad_value=1.0)
    dx = kernel_mod.timefloats_matmul_transposed_quantized(
        gd, qwq, qws, cfg=cfg, bm=bm, bc=bc, tn=tn, interpret=interpret)
    return dx[:m_dim, :k_dim]
