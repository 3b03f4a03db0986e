"""TimeFloats scalar products: the paper's 5-step algorithm in JAX.

Three matmul modes (see DESIGN.md §2):

- ``exact``     — faithful reproduction of the paper's pipeline. The
  alignment exponent is the *joint* max over the (input row, weight column)
  pair for each 64-element crossbar chunk, exactly as the time-domain
  tournament tree computes it. Pure jnp; used as oracle / for variability
  Monte Carlo / small-scale training.
- ``separable`` — the TPU-native adaptation: per (row × chunk) and
  (chunk × column) alignment so the fixed-point MAC is a plain int8
  dot_general on the MXU, with per-chunk rank-1 scales (microscaling,
  block=64=crossbar height). Strictly more truncation than ``exact``
  (quantified in tests), strictly MXU-friendly.
- ``pallas``    — the Pallas kernel implementation of ``separable``
  (kernels/timefloats_matmul.py); bit-identical to ``separable``.

The five steps (Fig. 2 of the paper) appear literally in
:func:`scalar_product_steps`; the batched matmuls are vectorizations of the
same arithmetic.

Training (DESIGN.md §3): :func:`linear`'s custom_vjp quantizes each operand
once, caches the quantized operands as residuals, and runs the backward
pass as transposed reads of the stored operands; :func:`linear_cached`
additionally accepts a per-step weight cache entry (models/common.py,
train/step.py).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import float8
from repro.core.float8 import E4M4, F8Fields, FloatFormat

Array = jax.Array


class NoiseParams(NamedTuple):
    """Process-variability model of Sec. III-D: C -> C * (1 + N(0, sigma)),
    applied separately to the exponent path (time-pulse representation of
    e_x + e_w) and to the mantissa path (crossbar product-sum)."""

    sigma_exp: float = 0.0
    sigma_mant: float = 0.0


@dataclasses.dataclass(frozen=True)
class TFConfig:
    """TimeFloats arithmetic configuration.

    block      — crossbar height / exponent-alignment block (paper: 64).
    adc_bits   — optional per-chunk partial-sum requantization modeling the
                 shared SAR ADC (paper hardware: 4 bits). ``None`` bypasses
                 (default for training quality; see DESIGN.md §2).
    adc_mode   — "dynamic": idealized auto-ranged full-scale (per call);
                 "fixed": worst-case full-scale block*(2^(m+1)-1)^2.
    mode       — "exact" | "separable" | "pallas".
    cache      — save already-quantized operands as custom_vjp residuals so
                 the backward pass is a transposed read of the stored operands
                 (DESIGN.md §3). ``False`` re-quantizes from the raw float
                 residuals in the backward pass — bit-identical outputs,
                 ~1.5x the quantization work (benchmarks/kernel_bench.py);
                 kept as the baseline and as a memory escape hatch.
    """

    fmt: FloatFormat = E4M4
    block: int = 64
    adc_bits: int | None = None
    adc_mode: str = "dynamic"
    mode: str = "exact"
    cache: bool = True

    @property
    def max_significand(self) -> int:
        return 2 * self.fmt.significand_scale - 1  # e.g. 31 for m=4

    @property
    def out_scale_bias(self) -> int:
        """Power-of-two to remove two integer significands + two exp biases."""
        return 2 * self.fmt.bias + 2 * self.fmt.man_bits


DEFAULT = TFConfig()


# ---------------------------------------------------------------------------
# The five steps, literally, for a single (x, w) pair of <=block length.
# Used by tests and by examples/quickstart.py as the readable reference.
# ---------------------------------------------------------------------------


def step1_exponent_add(fx: F8Fields, fw: F8Fields) -> Array:
    """Element-wise e_x + e_w on stored codes (the RC-discharge adder)."""
    return fx.exp.astype(jnp.int32) + fw.exp.astype(jnp.int32)


def step2_max_detect(s: Array, valid: Array) -> Array:
    """Largest summed exponent (the D-FF/MUX tournament tree)."""
    return jnp.max(jnp.where(valid, s, -(2**30)))


def step3_mantissa_scale(fx: F8Fields, s: Array, e_max: Array,
                         fmt: FloatFormat) -> Array:
    """Right-shift input significands by (E_max - s_i); shifts that exceed
    the significand width zero the term (the sparsity the paper notes)."""
    shift = jnp.clip(e_max - s, 0, 31)
    mhat = fx.significand(fmt) * fx.sign.astype(jnp.int32)
    # Hardware shift register: arithmetic shift on magnitude == floor on
    # non-negative; we shift the magnitude then restore sign.
    mag = jnp.abs(mhat) >> shift
    mag = jnp.where(shift > fmt.man_bits, 0, mag)  # all bits shifted out
    return jnp.sign(mhat) * mag


def step4_mac(mx_scaled: Array, fw: F8Fields, fmt: FloatFormat) -> Array:
    """Fixed-point scalar product against weight significands (crossbar)."""
    mw = fw.significand(fmt) * fw.sign.astype(jnp.int32)
    return jnp.sum(mx_scaled * mw)


def step5_renormalize(p: Array, e_max: Array, cfg: TFConfig) -> Array:
    """Digitize and rescale the product-sum back to floating point."""
    return p.astype(jnp.float32) * float8.exp2i(e_max - cfg.out_scale_bias)


def scalar_product_steps(x: Array, w: Array, cfg: TFConfig = DEFAULT) -> Array:
    """Full 5-step scalar product of two 1-D vectors (any length; chunked)."""
    (k,) = x.shape
    assert w.shape == (k,)
    pad = (-k) % cfg.block
    x = jnp.pad(x, (0, pad))
    w = jnp.pad(w, (0, pad))
    fx = float8.decompose(x, cfg.fmt)
    fw = float8.decompose(w, cfg.fmt)

    def chunk(c):
        sl = slice(c * cfg.block, (c + 1) * cfg.block)
        cx = jax.tree.map(lambda a: a[sl], fx)
        cw = jax.tree.map(lambda a: a[sl], fw)
        valid = cx.nonzero & cw.nonzero
        s = step1_exponent_add(cx, cw)
        e_max = step2_max_detect(s, valid)
        mx = step3_mantissa_scale(cx, s, e_max, cfg.fmt)
        mx = jnp.where(valid, mx, 0)
        p = step4_mac(mx, cw, cfg.fmt)
        p = _adc(p, cfg)
        return jnp.where(jnp.any(valid), step5_renormalize(p, e_max, cfg), 0.0)

    n_chunks = (k + pad) // cfg.block
    return jnp.sum(jnp.stack([chunk(c) for c in range(n_chunks)]))


def _adc(p: Array, cfg: TFConfig) -> Array:
    """Model of the shared SAR ADC quantizing a chunk partial sum.

    The paper fixes a 4-bit ADC but does not specify ranging; we provide an
    idealized auto-ranging mode (full scale = max |p| in the call) and a
    worst-case fixed mode. Disabled when adc_bits is None.
    """
    if cfg.adc_bits is None:
        return p
    levels = (1 << cfg.adc_bits) - 1
    if cfg.adc_mode == "fixed":
        fs = cfg.block * cfg.max_significand**2
        fs = jnp.asarray(fs, jnp.float32)
    else:
        fs = jnp.maximum(jnp.max(jnp.abs(p)).astype(jnp.float32), 1.0)
    q = jnp.round(p.astype(jnp.float32) / fs * levels) * (fs / levels)
    return q


# ---------------------------------------------------------------------------
# Exact-mode matmul: vectorized joint-max alignment, scan over K chunks.
# ---------------------------------------------------------------------------


def _pad_k(a: Array, block: int, axis: int) -> Array:
    pad = (-a.shape[axis]) % block
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def matmul_exact(
    x: Array,
    w: Array,
    cfg: TFConfig = DEFAULT,
    *,
    noise: NoiseParams | None = None,
    key: Array | None = None,
) -> Array:
    """(M, K) @ (K, N) with per-(row, column, chunk) joint max alignment.

    Memory is bounded by scanning over K chunks; each chunk materializes an
    (M, block, N) exponent-sum tensor — this is the faithful oracle, not the
    fast path.
    """
    assert x.ndim == 2 and w.ndim == 2 and x.shape[1] == w.shape[0]
    m_dim, k_dim = x.shape
    n_dim = w.shape[1]
    xp = _pad_k(x, cfg.block, 1)
    wp = _pad_k(w, cfg.block, 0)
    n_chunks = xp.shape[1] // cfg.block

    fx = float8.decompose(xp, cfg.fmt)
    fw = float8.decompose(wp, cfg.fmt)

    # (C, M, B) and (C, B, N) layouts for scanning.
    def to_cx(a):
        return a.reshape(m_dim, n_chunks, cfg.block).swapaxes(0, 1)

    def to_cw(a):
        return a.reshape(n_chunks, cfg.block, n_dim)

    cx = F8Fields(*(to_cx(a) for a in fx))
    cw = F8Fields(*(to_cw(a) for a in fw))

    if noise is not None and key is not None:
        keys = jax.random.split(key, n_chunks)
    else:
        keys = jnp.zeros((n_chunks, 2), jnp.uint32)

    def body(acc, inputs):
        cxc, cwc, kc = inputs
        # s[i, k, j] = e_x[i,k] + e_w[k,j]
        s = (cxc.exp.astype(jnp.int32)[:, :, None]
             + cwc.exp.astype(jnp.int32)[None, :, :])
        valid = cxc.nonzero[:, :, None] & cwc.nonzero[None, :, :]
        s_eff = jnp.where(valid, s, -(2**30))
        if noise is not None and noise.sigma_exp > 0:
            ke, _ = jax.random.split(kc)
            eps = jax.random.normal(ke, s.shape, jnp.float32) * noise.sigma_exp
            # the time-pulse representation of the sum is perturbed
            # multiplicatively; downstream max/subtract see the noisy value.
            s_noisy = jnp.where(valid, s.astype(jnp.float32) * (1.0 + eps),
                                -(2.0**30))
            e_max = jnp.max(s_noisy, axis=1)  # (M, N) float
            shift = jnp.clip(jnp.round(e_max[:, None, :] - s_noisy), 0, 31
                             ).astype(jnp.int32)
            e_max_i = jnp.round(e_max).astype(jnp.int32)
        else:
            e_max_i = jnp.max(s_eff, axis=1)  # (M, N)
            shift = jnp.clip(e_max_i[:, None, :] - s_eff, 0, 31)

        mx = cxc.significand(cfg.fmt)[:, :, None]  # (M, B, 1)
        mx = jnp.broadcast_to(mx, shift.shape)
        mx = mx >> shift
        mx = jnp.where(shift > cfg.fmt.man_bits, 0, mx)
        mx = jnp.where(valid, mx, 0)
        sx = cxc.sign.astype(jnp.int32)[:, :, None]
        mw = (cwc.significand(cfg.fmt) * cwc.sign.astype(jnp.int32))[None, :, :]
        p = jnp.sum(mx * sx * mw, axis=1)  # (M, N) int32
        p = _adc(p, cfg)
        if noise is not None and noise.sigma_mant > 0:
            _, km = jax.random.split(kc)
            eps = jax.random.normal(km, p.shape, jnp.float32) * noise.sigma_mant
            p = p.astype(jnp.float32) * (1.0 + eps)
        any_valid = jnp.any(valid, axis=1)
        contrib = jnp.where(
            any_valid,
            p.astype(jnp.float32)
            * float8.exp2i(e_max_i - cfg.out_scale_bias),
            0.0,
        )
        return acc + contrib, None

    acc0 = jnp.zeros((m_dim, n_dim), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (cx, cw, keys))
    return acc


# ---------------------------------------------------------------------------
# Separable (TPU-native) mode: microscaled int8 operands + MXU dot_generals.
# ---------------------------------------------------------------------------


class QuantizedOperand(NamedTuple):
    """Block-aligned integer operand.

    q:     int8, (..., C, B) for inputs / (C, B, ...) for weights — signed
           shifted significands in [-(2^(m+1)-1), 2^(m+1)-1].
    scale: f32 per-block scale 2^(a - bias - man_bits); zero blocks get
           scale with a=0 (q is zero there anyway).
    """

    q: Array
    scale: Array


def quantize_input(x: Array, cfg: TFConfig = DEFAULT) -> QuantizedOperand:
    """(M, K) -> q:(C, M, B) int8, scale:(C, M) f32."""
    _QUANT_TRACE_COUNTS[f"planes.{cfg.mode}"] += 1
    m_dim = x.shape[0]
    xp = _pad_k(x, cfg.block, 1)
    n_chunks = xp.shape[1] // cfg.block
    f = float8.decompose(xp, cfg.fmt)
    exp = f.exp.astype(jnp.int32).reshape(m_dim, n_chunks, cfg.block)
    nz = f.nonzero.reshape(m_dim, n_chunks, cfg.block)
    a = jnp.max(jnp.where(nz, exp, -(2**30)), axis=-1)  # (M, C)
    a = jnp.maximum(a, 0)
    shift = jnp.clip(a[:, :, None] - exp, 0, 31)
    mhat = f.significand(cfg.fmt).reshape(m_dim, n_chunks, cfg.block)
    q = mhat >> shift
    q = jnp.where(shift > cfg.fmt.man_bits, 0, q)
    q = q * f.sign.astype(jnp.int32).reshape(m_dim, n_chunks, cfg.block)
    scale = float8.exp2i(a - cfg.fmt.bias - cfg.fmt.man_bits)
    return QuantizedOperand(
        q=q.swapaxes(0, 1).astype(jnp.int8),  # (C, M, B)
        scale=scale.swapaxes(0, 1),  # (C, M)
    )


def quantize_weight(w: Array, cfg: TFConfig = DEFAULT) -> QuantizedOperand:
    """(K, N) -> q:(C, B, N) int8, scale:(C, N) f32."""
    _QUANT_TRACE_COUNTS[f"planes.{cfg.mode}"] += 1
    n_dim = w.shape[1]
    wp = _pad_k(w, cfg.block, 0)
    n_chunks = wp.shape[0] // cfg.block
    f = float8.decompose(wp, cfg.fmt)
    exp = f.exp.astype(jnp.int32).reshape(n_chunks, cfg.block, n_dim)
    nz = f.nonzero.reshape(n_chunks, cfg.block, n_dim)
    a = jnp.max(jnp.where(nz, exp, -(2**30)), axis=1)  # (C, N)
    a = jnp.maximum(a, 0)
    shift = jnp.clip(a[:, None, :] - exp, 0, 31)
    mhat = f.significand(cfg.fmt).reshape(n_chunks, cfg.block, n_dim)
    q = mhat >> shift
    q = jnp.where(shift > cfg.fmt.man_bits, 0, q)
    q = q * f.sign.astype(jnp.int32).reshape(n_chunks, cfg.block, n_dim)
    scale = float8.exp2i(a - cfg.fmt.bias - cfg.fmt.man_bits)
    return QuantizedOperand(q=q.astype(jnp.int8), scale=scale)


def matmul_separable_scan(x: Array, w: Array, cfg: TFConfig = DEFAULT) -> Array:
    """(M,K) @ (K,N) via per-chunk int8 MACs with rank-1 scales, scanned
    over K chunks. Bit-exact spec of the Pallas kernel (kernels/ref.py);
    also the path that models the per-chunk ADC quantizer.
    """
    qx = quantize_input(x, cfg)
    qw = quantize_weight(w, cfg)
    return matmul_from_quantized(qx, qw, cfg)


def dequantize_input(qx: "QuantizedOperand", k_dim: int, dtype=jnp.bfloat16
                     ) -> Array:
    """(C,M,B) int8 + (C,M) scale -> (M,K) block-aligned values. Exact:
    |q| <= 31 (5 bits) times a power-of-two scale is representable in bf16."""
    c, m, b = qx.q.shape
    v = qx.q.astype(jnp.float32) * qx.scale[:, :, None]
    return v.swapaxes(0, 1).reshape(m, c * b)[:, :k_dim].astype(dtype)


def dequantize_weight(qw: "QuantizedOperand", k_dim: int, dtype=jnp.bfloat16
                      ) -> Array:
    c, b, n = qw.q.shape
    v = qw.q.astype(jnp.float32) * qw.scale[:, None, :]
    return v.reshape(c * b, n)[:k_dim].astype(dtype)


def matmul_separable(x: Array, w: Array, cfg: TFConfig = DEFAULT) -> Array:
    """Fast XLA form of the separable mode: block-align-quantize, dequantize
    (exact — values are 5-bit significands times power-of-two scales), then
    ONE dense matmul with f32 accumulation.

    Mathematically identical to `matmul_separable_scan` up to f32 summation
    order (no int overflow: products are <=10-bit significands); asserted
    close in tests. The int8-MAC execution lives in the Pallas kernel
    (deployment path); this is the XLA/dry-run path. The per-chunk ADC model
    requires the scan form (dispatches automatically when adc_bits is set).
    """
    if cfg.adc_bits is not None:
        return matmul_separable_scan(x, w, cfg)
    k_dim = x.shape[1]
    xd = dequantize_input(quantize_input(x, cfg), k_dim)
    wd = dequantize_weight(quantize_weight(w, cfg), k_dim)
    return jax.lax.dot_general(xd, wd, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def matmul_separable_transposed(g: Array, qw: QuantizedOperand, k_dim: int,
                                cfg: TFConfig = DEFAULT) -> Array:
    """dx = g @ W^T as a *transposed read* of the stored weight planes.

    The stored operand keeps its forward-pass alignment: chunks along K
    with per-(K-chunk, N-column) scales — exactly the int8 planes the
    crossbar holds. Nothing is re-decomposed: the planes are dequantized
    (exact: 5-bit significands times pow2 scales) into W's natural (K, N)
    layout and the contraction over N is expressed in the dot_general
    dimension numbers, so no (N, K) copy of W^T is ever materialized and
    the dot lowers to a plain transposed-B GEMM. Only the streamed operand
    ``g`` is quantized (once, along its own contraction dim N). See
    DESIGN.md §3.

    The per-chunk ADC is a forward-read model; transposed reads are modeled
    ADC-free (DESIGN.md §3), so this is a single f32-accumulated contraction
    in every configuration.
    """
    n_dim = g.shape[1]
    qg = quantize_input(g, cfg)
    gd = dequantize_input(qg, n_dim)             # (M2, N)
    c, b, _ = qw.q.shape
    wv = dequantize_weight(qw, c * b)            # (Kpad, N), stored codes
    dx = jax.lax.dot_general(gd, wv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (M2, Kpad)
    return dx[:, :k_dim]


def matmul_separable_outer(qx: QuantizedOperand, g: Array, k_dim: int,
                           cfg: TFConfig = DEFAULT) -> Array:
    """dW = x^T @ g as a transposed read of the stored activation planes.

    Mirror image of :func:`matmul_separable_transposed`: the activations
    written during the forward pass are read back (same codes, same
    truncation — no re-quantization), ``g`` is quantized once as the
    streamed operand (chunked along M, its contraction dim), and the
    contraction over M is expressed in the dimension numbers (a
    transposed-A GEMM). This is the outer-product accumulation the paper's
    in-situ update consumes.
    """
    m2, n_dim = g.shape
    qg = quantize_weight(g, cfg)
    gd = dequantize_weight(qg, m2)               # (M2, N)
    c, _, b = qx.q.shape
    xd = dequantize_input(qx, c * b)             # (M2, Kpad), stored codes
    dw = jax.lax.dot_general(xd, gd, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Kpad, N)
    return dw[:k_dim]


def matmul_from_quantized(qx: QuantizedOperand, qw: QuantizedOperand,
                          cfg: TFConfig = DEFAULT) -> Array:
    def body(acc, inputs):
        q_x, s_x, q_w, s_w = inputs
        p = jax.lax.dot_general(
            q_x, q_w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        p = _adc(p, cfg)
        return acc + p.astype(jnp.float32) * s_x[:, None] * s_w[None, :], None

    m_dim = qx.q.shape[1]
    n_dim = qw.q.shape[2]
    acc0 = jnp.zeros((m_dim, n_dim), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (qx.q, qx.scale, qw.q, qw.scale))
    return acc


# ---------------------------------------------------------------------------
# Dispatch + the training primitive (custom_vjp: fwd AND bwd in-crossbar).
# ---------------------------------------------------------------------------


def matmul(x: Array, w: Array, cfg: TFConfig = DEFAULT) -> Array:
    """2-D TimeFloats matmul in the configured mode."""
    if cfg.mode == "exact":
        return matmul_exact(x, w, cfg)
    if cfg.mode == "separable":
        return matmul_separable(x, w, cfg)
    if cfg.mode == "pallas":
        from repro.kernels import ops  # local import: kernels dep is optional

        return ops.timefloats_matmul(x, w, cfg)
    raise ValueError(f"unknown TimeFloats mode: {cfg.mode!r}")


def _pow2_scale(amax: Array, cfg: TFConfig) -> Array:
    """The power-of-two prescale for a tensor whose max |value| is ``amax``
    (computed in the tensor's own dtype)."""
    # target the max exponent so the full [0, 2^e-1] code range is usable
    target = cfg.fmt.max_exp_code - 1 - cfg.fmt.bias
    log2a = jnp.floor(jnp.log2(jnp.maximum(amax, 1e-30)))
    return float8.exp2i(
        jnp.where(amax > 0, target - log2a, 0.0).astype(jnp.int32))


def _pow2_prescale(a: Array, cfg: TFConfig) -> tuple[Array, Array]:
    """Per-tensor power-of-two scale mapping amax near the top of the FP8
    range. Power-of-two scaling is exact in FP8 (only the exponent reference
    moves — on the chip this is the programmable bias voltage V_B / reference
    subtraction; in FP8-training practice it is the standard amax scale).
    Returns (scaled array, scale) with ``quantizable = a * scale``.
    """
    scale = _pow2_scale(jnp.max(jnp.abs(a)), cfg)
    return a * scale, scale


# ---------------------------------------------------------------------------
# Two-pass block alignment (DESIGN.md §2): the separable operand as values.
#
# E4M4 rounding (nearest even, flush below 2^-bias, saturate at the largest
# code) is monotone in |v|, so a block's shared exponent is the exponent of
# the rounded block max, and the tensor's amax is the max of the block
# maxima. Pass 1 reduces |x| to per-block maxima (and from them the pow2
# prescale); pass 2 is elementwise: sign(x) * floor(r(|x| s) / u_b) * u_b
# with u_b = 2^(a_b - bias - man_bits) the block's unit. The result is
# bit-identical to dequantize_*(quantize_*(_pow2_prescale(x))) — exact in
# bf16, since |q| <= 2^(m+1) - 1 times a power of two — without building
# the int8 planes.
# ---------------------------------------------------------------------------


def _pow2(e: Array) -> Array:
    """Exact f32 2^e for int32 e in the normal range, from its bits."""
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def round_to_fmt(v: Array, fmt: FloatFormat) -> Array:
    """``float8.quantize(v, fmt)`` for f32 ``v >= 0``, on the f32 bits:
    round to nearest even on ``fmt.man_bits`` bits, flush below
    2^-bias, saturate at ``fmt.max_value``."""
    drop = 23 - fmt.man_bits
    b = jax.lax.bitcast_convert_type(v, jnp.uint32)
    b = b + jnp.uint32((1 << (drop - 1)) - 1) + ((b >> drop) & 1)
    b = b & jnp.uint32((0xFFFFFFFF >> drop) << drop)
    r = jax.lax.bitcast_convert_type(b, jnp.float32)
    return jnp.minimum(jnp.where(r >= fmt.min_normal, r, 0.0), fmt.max_value)


def _blocks(a: Array, block: int, axis: int) -> Array:
    """(M, K) -> (M, C, B) for axis 1; (K, N) -> (C, B, N) for axis 0;
    zero-padded to whole blocks like :func:`_pad_k`."""
    a = _pad_k(a, block, axis)
    if axis == 1:
        return a.reshape(a.shape[0], -1, block)
    return a.reshape(-1, block, a.shape[1])


def _block_max(x: Array, block: int, axis: int) -> Array:
    """Pass 1: max |x| per block, in x's dtype, kept as a broadcastable
    (M, C, 1) / (C, 1, N)."""
    return jnp.max(jnp.abs(_blocks(x, block, axis)), axis=axis + 1,
                   keepdims=True)


def aligned_values(x: Array, r: Array, top: Array,
                   fmt: FloatFormat) -> Array:
    """Pass 2's arithmetic, shared with the Pallas kernel
    (kernels/block_align.py): the signed block-aligned value of each
    element from ``x``, ``r = round_to_fmt(|x| s)`` and ``top``, the rounded
    max |x| s of the element's block (broadcastable to ``r``)."""
    e = (jax.lax.bitcast_convert_type(jnp.maximum(top, fmt.min_normal),
                                      jnp.int32) >> 23) - 127
    unit, inv = _pow2(e - fmt.man_bits), _pow2(fmt.man_bits - e)
    mag = jnp.floor(r * inv) * unit
    return jnp.where((x < 0) & (mag > 0), -mag, mag)  # zeros stay +0


def _aligned(x: Array, bmax: Array, s: Array, cfg: TFConfig,
             axis: int) -> Array:
    """Pass 2: the block-aligned values of ``x * s`` in x's own layout."""
    fmt = cfg.fmt
    top = round_to_fmt(bmax.astype(jnp.float32) * s, fmt)
    xb = _blocks(x, cfg.block, axis)
    r = round_to_fmt(jnp.abs(xb).astype(jnp.float32) * s, fmt)
    v = aligned_values(xb, r, top, fmt)
    v = v.reshape(x.shape[0], -1) if axis == 1 else v.reshape(-1, x.shape[1])
    return jax.lax.slice_in_dim(v, 0, x.shape[axis], axis=axis).astype(
        jnp.bfloat16)


def block_align(x: Array, cfg: TFConfig, axes: tuple) -> tuple:
    """2-D ``x`` -> ((bf16 block-aligned values of ``x * s``, one per entry
    of ``axes``), prescale s), with blocks of ``cfg.block`` along each
    axis: 1 (the last) for inputs and dx's cotangent, 0 for weights and
    dW's. Bit-identical to ``dequantize_input(quantize_input(xs))`` resp.
    ``dequantize_weight(quantize_weight(xs))`` with ``xs, s =
    _pow2_prescale(x)``; asking for both layouts reads ``x`` once.

    On a TPU pass 2 is the Pallas kernel (kernels/block_align.py), which
    finds the block maxima inside its tiles, so pass 1 reduces to the amax
    alone; elsewhere, and in a program XLA partitions over a mesh (the
    kernel has no partitioning rule), pass 1 keeps the block maxima and
    pass 2 broadcasts them."""
    for a in axes:
        _QUANT_TRACE_COUNTS[f"block_align.axis{a}"] += 1
    x = jax.lax.stop_gradient(x)  # the aligned values are flat in x a.e.
    from repro.kernels import dispatch  # local import: kernels dep is optional

    d = dispatch.current()
    if (d.use_pallas and cfg.block % 8 == 0
            and not dispatch.auto_partitioned()):
        from repro.kernels.block_align import block_align_pallas

        s = _pow2_scale(jnp.max(jnp.abs(x)), cfg)
        return block_align_pallas(x, s, axes=axes, block=cfg.block,
                                  fmt=cfg.fmt, interpret=d.interpret), s
    bmax = [_block_max(x, cfg.block, a) for a in axes]
    s = _pow2_scale(jnp.max(bmax[0]), cfg)
    return tuple(_aligned(x, b, s, cfg, a) for a, b in zip(axes, bmax)), s


def _scaled_matmul(x: Array, w: Array, cfg: TFConfig) -> Array:
    """``matmul`` of the pow2-prescaled operands, scales divided out: the
    forward of :func:`linear`. Separable mode without an ADC model goes
    through the prepared values, as its custom_vjp does."""
    if _as_values(cfg):
        px, pw = prepare_input(x, cfg), prepare_weight(w, cfg)
        return _matmul_prepared(px, pw, x.shape[0], x.shape[1], w.shape[1],
                                cfg) / (px.scale * pw.scale)
    _record_op("fwd", x.shape[0], x.shape[1], w.shape[1])
    xs, sx = _pow2_prescale(x, cfg)
    ws, sw = _pow2_prescale(w, cfg)
    return matmul(xs, ws, cfg) / (sx * sw)


# ---------------------------------------------------------------------------
# Quantized-operand cache (DESIGN.md §3): operands are prescaled + quantized
# exactly once; the backward pass is a transposed read of the stored planes.
# ---------------------------------------------------------------------------


class PreparedOperand(NamedTuple):
    """Mode-appropriate quantized form of one prescaled operand — the unit
    of the quantized-operand cache (DESIGN.md §3).

    scale — () f32 per-tensor pow2 amax prescale (exact in FP8; the
            programmable reference V_B on chip). The quantized payload
            encodes ``operand * scale``; products are divided by the two
            operand scales on the way out.
    q     — pallas mode, and separable mode with an ADC model: block-
            aligned int8 planes + per-chunk scales (the at-rest crossbar
            representation). None otherwise.
    fq    — exact mode: the FP8-quantized scaled values (f32).
            ``float8.decompose`` is exactly idempotent on these, so feeding
            them back through ``matmul_exact`` reproduces the uncached bits.
            None in separable/pallas modes.
    v     — separable mode without an ADC model: the block-aligned values
            of the scaled operand as bf16, in the operand's own layout
            (:func:`block_align`; exactly what dequantizing ``q`` would
            give).
            None otherwise.

    Pytree contract (DESIGN.md §3, scanned stacks): as a NamedTuple this is
    a registered JAX pytree whose ``None`` fields are empty subtrees, so a
    *stack* of prepared weights — every leaf carrying a leading ``(layers,)``
    dim, built by ``jax.vmap(prepare_weight)`` — threads through
    ``lax.scan``/``vmap`` as an ordinary operand and slices back into valid
    per-layer entries. Within one ``TFConfig`` the None-pattern is fixed
    (mode and adc_bits decide q vs fq vs v), so the tree structure is
    scan-stable.
    ``tests/test_cache.py::test_prepared_operand_pytree_roundtrip`` pins
    this.
    """

    scale: Array
    q: QuantizedOperand | None
    fq: Array | None
    v: Array | None = None


# Trace-time quantization census. Each prepare_* call increments ONCE per
# Python invocation, i.e. once per *trace* — a call inside a lax.scan body
# or under vmap counts 1 no matter the trip count / batch size. That makes
# the counter a structural proof: a jitted train step whose trace shows
# exactly one prepare_weight per dense-eligible leaf performs ALL its weight
# quantization in build_weight_cache (hoisted, once per optimizer step);
# any registry miss inside the loss would add a per-call-site count (and
# would *execute* once per microbatch/layer). The same census counts the
# quantizers by path: "block_align.axis1"/"block_align.axis0" for the
# two-pass values (by the axis the blocks run along) and
# "planes.<mode>" for quantize_input/quantize_weight (by cfg.mode). Read/
# reset via quant_trace_counts / reset_quant_trace_counts (a Counter:
# absent keys read 0); asserted by tests/test_cache*.py and reported by
# benchmarks/kernel_bench.py.
_QUANT_TRACE_COUNTS: collections.Counter = collections.Counter()


def quant_trace_counts() -> collections.Counter:
    return collections.Counter(_QUANT_TRACE_COUNTS)


def reset_quant_trace_counts() -> None:
    _QUANT_TRACE_COUNTS.clear()


# ---------------------------------------------------------------------------
# Op-level trace census (DESIGN.md §6). Like the prepare_* counters above,
# records are appended at Python trace time — but each record carries the
# static matmul shape, a crossbar-access tag, and the execution multiplier
# accumulated from every enclosing census_scale() context (layer-scan trip
# counts, the MoE expert vmap and dispatch-chunk scan, grad-accumulation
# microbatches), so ONE abstract trace of a forward program yields its
# full crossbar read census:
#
#   fwd     — forward read:            y  = x @ W          (ADC digitizes)
#   bwd_dx  — transposed read:         dx = g @ W^T        (ADC-free, §3)
#   bwd_dw  — outer-product read:      dW = x^T @ g        (ADC-free, §3)
#
# Shapes are the (M, K, N) of the equivalent crossbar matmul with K the
# contraction dim (so ceil(K/block) is the chunk count per output): bwd_dx
# is (M, N_fwd, K_fwd) — it contracts over the forward output columns —
# and bwd_dw is (K_fwd, M_fwd, N_fwd).
#
# Only the *primal* paths record (tag "fwd"): capture a census by tracing
# the forward/loss function WITHOUT differentiation, then synthesize the
# training tags with backward_census(). Rationale: the primal Python body
# runs exactly once per call site inside every trace context (verified per
# family in tests/test_hw.py), whereas JAX's custom_vjp machinery invokes
# the fwd/bwd rules at mechanism-dependent times — the bwd callback during
# transposition (outside any census_scale extent), the fwd rule 0–2x
# depending on scan/vmap nesting — so recording there over- or
# under-counts. The backward synthesis is structural and exact: the §3
# custom_vjp performs exactly one transposed dx read and one outer dW read
# per differentiated linear, with the shapes above.
# hw/schedule.py turns a census into energy/latency/TOPS-per-W.
# ---------------------------------------------------------------------------


class OpRecord(NamedTuple):
    """One trace-time crossbar matmul: tag, (M, K, N), static multiplier."""

    tag: str
    m: int
    k: int
    n: int
    mult: int


_OP_CENSUS: Optional[list] = None
_CENSUS_SCALE: int = 1


@contextlib.contextmanager
def op_census():
    """Collect OpRecords for everything traced inside the context:

        with op_census() as events:
            jax.eval_shape(loss_fn, params, batch)   # trace, no FLOPs
        cost = hw.schedule.census_cost(backward_census(events))

    Trace a FORWARD program (see the header above); expand training
    censuses with backward_census(). Nested uses stack (each context sees
    only its own records).
    """
    global _OP_CENSUS
    prev = _OP_CENSUS
    events: list = []
    _OP_CENSUS = events
    try:
        yield events
    finally:
        _OP_CENSUS = prev


@contextlib.contextmanager
def census_scale(n: int):
    """Multiply the census weight of records traced inside by ``n`` — used
    around lax.scan calls (the body traces once for ``n`` executions) and
    the MoE expert vmap. No-ops cheaply when no census is active."""
    global _CENSUS_SCALE
    prev = _CENSUS_SCALE
    _CENSUS_SCALE = prev * int(n)
    try:
        yield
    finally:
        _CENSUS_SCALE = prev


def _record_op(tag: str, m: int, k: int, n: int) -> None:
    if _OP_CENSUS is not None:
        _OP_CENSUS.append(OpRecord(tag, int(m), int(k), int(n),
                                   _CENSUS_SCALE))


def backward_census(events) -> list:
    """Expand a forward census into the full training-step census: every
    differentiated linear's forward read (M, K, N) is joined by its
    transposed dx read (M, N, K) and outer dW read (K, M, N) — exactly
    what the §3 custom_vjp backward executes against the stored planes."""
    out = list(events)
    for ev in events:
        if ev.tag == "fwd":
            out.append(OpRecord("bwd_dx", ev.m, ev.n, ev.k, ev.mult))
            out.append(OpRecord("bwd_dw", ev.k, ev.m, ev.n, ev.mult))
    return out


def _as_values(cfg: TFConfig) -> bool:
    """Separable mode without an ADC model reads its operands as bf16
    block-aligned values (PreparedOperand.v); every other int8 path reads
    the planes."""
    return cfg.mode == "separable" and cfg.adc_bits is None


def _prepare(a: Array, cfg: TFConfig, axis: int, quantize) -> PreparedOperand:
    if _as_values(cfg):
        (v,), s = block_align(a, cfg, (axis,))
        return PreparedOperand(scale=s, q=None, fq=None, v=v)
    xs, s = _pow2_prescale(a, cfg)
    if cfg.mode == "exact":
        return PreparedOperand(scale=s, q=None, fq=float8.quantize(xs, cfg.fmt))
    return PreparedOperand(scale=s, q=quantize(xs, cfg), fq=None)


def prepare_input(x2: Array, cfg: TFConfig = DEFAULT) -> PreparedOperand:
    """(M, K) activation -> cache entry (quantized once; read by fwd + dW)."""
    _QUANT_TRACE_COUNTS["prepare_input"] += 1
    return _prepare(x2, cfg, 1, quantize_input)


def prepare_weight(w: Array, cfg: TFConfig = DEFAULT) -> PreparedOperand:
    """(K, N) weight -> cache entry (quantized once; read by fwd + dx)."""
    _QUANT_TRACE_COUNTS["prepare_weight"] += 1
    return _prepare(w, cfg, 0, quantize_weight)


def _matmul_prepared(px: PreparedOperand, pw: PreparedOperand, m_dim: int,
                     k_dim: int, n_dim: int, cfg: TFConfig) -> Array:
    """Forward product from cache entries; bit-identical to
    ``matmul(xs, ws, cfg)`` on the prescaled operands in every mode."""
    _record_op("fwd", m_dim, k_dim, n_dim)
    if cfg.mode == "exact":
        return matmul_exact(px.fq, pw.fq, cfg)
    if cfg.mode == "pallas":
        from repro.kernels import ops  # local import: kernels dep is optional

        return ops.quantized_matmul(px.q, pw.q, cfg=cfg)[:m_dim, :n_dim]
    if cfg.adc_bits is not None:
        return matmul_from_quantized(px.q, pw.q, cfg)
    return jax.lax.dot_general(px.v, pw.v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bwd_prepared(cfg: TFConfig, px: PreparedOperand, pw: PreparedOperand,
                  g2: Array, k_dim: int) -> tuple[Array, Array]:
    """dx = g @ W^T and dW = x^T @ g from the stored operands.

    Exact mode re-MACs the stored FP8 values with joint alignment (the
    oracle; bit-identical to the pre-cache implementation). Separable and
    pallas modes read the stored operands transposed — same codes, same
    truncation, no re-decomposition (DESIGN.md §3). Without an ADC model
    separable mode aligns the cotangent in both layouts under one
    prescale and contracts the stored values in place: dx over N against
    W's (K, N), dW over M against x's (M, K).
    """
    if _as_values(cfg):
        (gx, gw), sg = block_align(g2, cfg, (1, 0))
        dx = jax.lax.dot_general(gx, pw.v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dw = jax.lax.dot_general(px.v, gw, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return dx / (sg * pw.scale), dw / (px.scale * sg)
    gs, sg = _pow2_prescale(g2, cfg)
    if cfg.mode == "exact":
        dx = matmul_exact(gs, pw.fq.T, cfg) / (sg * pw.scale)
        dw = matmul_exact(px.fq.T, gs, cfg) / (px.scale * sg)
        return dx, dw
    if cfg.mode == "pallas" and cfg.adc_bits is None:
        from repro.kernels import ops  # local import: kernels dep is optional

        dx = ops.timefloats_matmul_transposed(gs, pw.q, k_dim=k_dim, cfg=cfg)
    else:
        dx = matmul_separable_transposed(gs, pw.q, k_dim, cfg)
    # The dW outer product is the in-situ *update* computation, not a
    # crossbar read — it stays on the XLA path in all int8 modes (and is
    # therefore bit-identical between separable and pallas).
    dw = matmul_separable_outer(px.q, gs, k_dim, cfg)
    return dx / (sg * pw.scale), dw / (px.scale * sg)


def linear(x: Array, w: Array, cfg: TFConfig = DEFAULT) -> Array:
    """Training linear layer: y = x @ w with TimeFloats arithmetic.

    Train-in-memory means the backward pass also runs in the crossbar:
    dx = g @ W^T is the transposed-read of the same stored FP8 weights, and
    dW = x^T @ g is the outer-product read of the stored activations. The
    forward pass quantizes each operand exactly once and saves the
    *quantized* operands as residuals (cfg.cache, DESIGN.md §3); the
    backward pass consumes them directly, quantizing only the streamed
    gradient. The quantizer itself uses a straight-through estimator
    (standard QAT), and operands get per-tensor power-of-two amax
    prescaling (exact in FP8; required so activations/gradients use the E4
    exponent range).

    Accepts arbitrary leading batch dims on x.
    """
    statics = (cfg, x.shape, jnp.dtype(x.dtype).name, jnp.dtype(w.dtype).name)
    return _linear_p(statics, x, w)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linear_p(statics, x, w):
    cfg = statics[0]
    lead = x.shape[:-1]
    y = _scaled_matmul(x.reshape(-1, x.shape[-1]), w, cfg)
    return y.reshape(*lead, w.shape[-1])


def _linear_p_fwd(statics, x, w):
    cfg = statics[0]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if cfg.cache:
        px = prepare_input(x2, cfg)
        pw = prepare_weight(w, cfg)
        y = _matmul_prepared(px, pw, x2.shape[0], x2.shape[1], w.shape[1],
                             cfg) / (px.scale * pw.scale)
        res = (px, pw)
    else:
        y = _scaled_matmul(x2, w, cfg)
        res = (x2, w)
    return y.reshape(*lead, w.shape[-1]), res


def _linear_p_bwd(statics, res, g):
    cfg, x_shape, x_dt, w_dt = statics
    g2 = g.reshape(-1, g.shape[-1])
    if cfg.cache:
        px, pw = res
    else:
        x2, w = res
        px = prepare_input(x2, cfg)
        pw = prepare_weight(w, cfg)
    dx, dw = _bwd_prepared(cfg, px, pw, g2, x_shape[-1])
    return dx.reshape(x_shape).astype(x_dt), dw.astype(w_dt)


_linear_p.defvjp(_linear_p_fwd, _linear_p_bwd)


def linear_cached(x: Array, w: Array, pw: PreparedOperand,
                  cfg: TFConfig = DEFAULT) -> Array:
    """:func:`linear` with the weight's cache entry precomputed.

    ``pw = prepare_weight(w, cfg)`` may be built once per optimizer step —
    outside the microbatch scan and the autodiff trace — and shared by every
    forward/dx read of that weight (models/common.py weight_cache_scope,
    train/step.py). Gradients still flow to ``w`` (which participates only
    as the gradient attachment point; its stored codes are ``pw``); the
    cache entry itself is a non-differentiable read-only view of the
    crossbar state and receives zero/float0 cotangents.
    """
    assert w.ndim == 2
    statics = (cfg, x.shape, jnp.dtype(x.dtype).name, jnp.dtype(w.dtype).name)
    return _linear_cached_p(statics, x, w, pw)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linear_cached_p(statics, x, w, pw):
    y, _ = _linear_cached_core(statics, x, w, pw)
    return y


def _linear_cached_core(statics, x, w, pw):
    cfg = statics[0]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    px = prepare_input(x2, cfg)
    y = _matmul_prepared(px, pw, x2.shape[0], x2.shape[1], w.shape[1],
                         cfg) / (px.scale * pw.scale)
    return y.reshape(*lead, w.shape[-1]), (px, pw)


def _linear_cached_p_fwd(statics, x, w, pw):
    return _linear_cached_core(statics, x, w, pw)


def _zero_cotangent(tree):
    """Zero (float leaves) / float0 (integer leaves) cotangents for the
    non-differentiable cache entry passed through the custom_vjp."""
    return jax.tree.map(
        lambda a: jnp.zeros_like(a)
        if jnp.issubdtype(a.dtype, jnp.inexact)
        else np.zeros(a.shape, jax.dtypes.float0), tree)


def _linear_cached_p_bwd(statics, res, g):
    cfg, x_shape, x_dt, w_dt = statics
    px, pw = res
    g2 = g.reshape(-1, g.shape[-1])
    dx, dw = _bwd_prepared(cfg, px, pw, g2, x_shape[-1])
    return (dx.reshape(x_shape).astype(x_dt), dw.astype(w_dt),
            _zero_cotangent(pw))


_linear_cached_p.defvjp(_linear_cached_p_fwd, _linear_cached_p_bwd)


def dot(x: Array, w: Array, cfg: TFConfig = DEFAULT, *, use_vjp: bool = True):
    """Convenience: general ...K @ KN contraction with the training vjp."""
    if use_vjp:
        return linear(x, w, cfg)
    lead = x.shape[:-1]
    y = matmul(x.reshape(-1, x.shape[-1]), w, cfg)
    return y.reshape(*lead, w.shape[-1])


def expected_sparsity(x: Array, w: Array, cfg: TFConfig = DEFAULT) -> Array:
    """Fraction of chunk terms zeroed by shift-truncation (paper: 'enhancing
    sparsity'). Reported by benchmarks; exact-mode bookkeeping."""
    xp = _pad_k(x, cfg.block, 1)
    wp = _pad_k(w, cfg.block, 0)
    fx = float8.decompose(xp, cfg.fmt)
    fw = float8.decompose(wp, cfg.fmt)
    m_dim, k_pad = xp.shape
    n_dim = wp.shape[1]
    c = k_pad // cfg.block
    ex = fx.exp.astype(jnp.int32).reshape(m_dim, c, cfg.block)
    ew = fw.exp.astype(jnp.int32).reshape(c, cfg.block, n_dim)
    s = ex[:, :, :, None] + ew[None, :, :, :]  # (M, C, B, N)
    valid = (fx.nonzero.reshape(m_dim, c, cfg.block)[:, :, :, None]
             & fw.nonzero.reshape(c, cfg.block, n_dim)[None])
    e_max = jnp.max(jnp.where(valid, s, -(2**30)), axis=2, keepdims=True)
    dropped = valid & ((e_max - s) > cfg.fmt.man_bits)
    return jnp.sum(dropped) / jnp.maximum(jnp.sum(valid), 1)
