"""Pallas TPU kernel for the TimeFloats separable block-aligned int8 matmul.

Hardware mapping (DESIGN.md §2): one 64-element crossbar chunk = one int8
dot_general of contraction depth 64 on the MXU, with the per-chunk exponent
alignment folded into rank-1 f32 scales. The kernel consumes pre-quantized
operands (sign-folded shifted significands in [-31, 31] for E4M4):

    qx: (C, M, B) int8    sx: (C, M) f32      # per (row, chunk) scale
    qw: (C, B, N) int8    sw: (C, N) f32      # per (chunk, col) scale
    out: (M, N) f32 = Σ_c (qx[c] @ qw[c]) * sx[c,:,None] * sw[c,None,:]

Tiling: grid (M/bm, N/bn, C/bc), the chunk dim innermost so the output tile
stays resident in VMEM across the reduction (standard accumulate pattern,
initialized at c==0). VMEM working set per step:

    qx tile  bc*bm*64  int8   (e.g. 8*256*64   = 128 KiB)
    qw tile  bc*64*bn  int8   (e.g. 8*64*256   = 128 KiB)
    out tile bm*bn     f32    (e.g. 256*256*4  = 256 KiB)
    scales   bc*(bm+bn) f32   (    8*512*4     =  16 KiB)
    total ≈ 528 KiB « 16 MiB v5e VMEM — leaves headroom for double buffering.

MXU alignment: bm, bn default 256 (multiples of 128); the contraction depth
is the crossbar height B=64 — half an MXU pass. `TFConfig(block=128)`
("ganged crossbars", a beyond-paper knob evaluated in §Perf) fills the MXU
fully; accuracy delta is measured in tests/benchmarks.

ADC modeling: the kernel supports `adc_bits` with `adc_mode="fixed"` (static
full-scale — bit-exact with the oracle). Dynamic auto-ranging needs a global
max and is served by the XLA path (ops.py dispatches).

Compiled on a TPU; in interpret mode on every other backend
(kernels/dispatch), where tests/test_kernels.py checks it against the
oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.timefloats import TFConfig
from repro.kernels import dispatch

Array = jax.Array


def _kernel(qx_ref, sx_ref, qw_ref, sw_ref, out_ref, *, bc: int,
            adc_bits: int | None, adc_fs: float):
    """One (bm, bn) output tile; accumulates bc chunks per grid step."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref[...]
    for k in range(bc):  # static unroll over chunks in this K-tile
        p = jax.lax.dot_general(
            qx_ref[k], qw_ref[k],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        pf = p.astype(jnp.float32)
        if adc_bits is not None:
            levels = float((1 << adc_bits) - 1)
            pf = jnp.round(pf / adc_fs * levels) * (adc_fs / levels)
        acc = acc + pf * sx_ref[k][:, None] * sw_ref[k][None, :]
    out_ref[...] = acc


def timefloats_matmul_quantized(
    qx: Array, sx: Array, qw: Array, sw: Array,
    *,
    cfg: TFConfig,
    bm: int = 256,
    bn: int = 256,
    bc: int = 8,
    interpret: bool | None = None,
) -> Array:
    """pallas_call wrapper on pre-quantized/padded operands.

    Expects M % bm == N % bn == C % bc == 0 (ops.py pads). ``interpret``
    defaults to the backend's choice (kernels/dispatch).
    """
    if interpret is None:
        interpret = dispatch.current().interpret
    n_chunks, m_dim, blk = qx.shape
    n_dim = qw.shape[2]
    assert qw.shape == (n_chunks, blk, n_dim), (qx.shape, qw.shape)
    assert m_dim % bm == 0 and n_dim % bn == 0 and n_chunks % bc == 0

    if cfg.adc_bits is not None and cfg.adc_mode != "fixed":
        raise ValueError("pallas kernel supports adc_mode='fixed' only; "
                         "dynamic ranging needs a global max (XLA path)")
    adc_fs = float(cfg.block * cfg.max_significand**2)

    grid = (m_dim // bm, n_dim // bn, n_chunks // bc)
    kernel = functools.partial(_kernel, bc=bc, adc_bits=cfg.adc_bits,
                               adc_fs=adc_fs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bm, blk), lambda i, j, c: (c, i, 0)),
            pl.BlockSpec((bc, bm), lambda i, j, c: (c, i)),
            pl.BlockSpec((bc, blk, bn), lambda i, j, c: (c, 0, j)),
            pl.BlockSpec((bc, bn), lambda i, j, c: (c, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), jnp.float32),
        interpret=interpret,
    )(qx, sx, qw, sw)


# ---------------------------------------------------------------------------
# Transposed read: dx = g @ W^T against the *stored* weight planes
# (DESIGN.md §3). The weight operand arrives in exactly the layout the
# forward kernel consumed — (C, Bk, N) int8 planes with (C, N) scales — so
# the backward pass re-reads the crossbar contents instead of re-quantizing
# a materialized W^T. The streamed gradient arrives already quantized along
# its own contraction dim N and dequantized to bf16, in its natural (M, N)
# layout: a 5-bit significand times a power-of-two scale is exact in bf16,
# so this carries the same values as the (D, M, Bn) int8 planes plus
# (D, M) scales, in a layout whose blocks the TPU tiles without slicing
# lanes at 64-element offsets.
#
#     out: (M, C*Bk) f32,  out[m, (c,b)] = Σ_n g[m,n] · qw[c,b,n] · sw[c,n]
#
# The per-column weight scale sw[c, n] varies along the contraction, so it
# cannot be hoisted into a rank-1 post-scale like the forward kernel's; the
# kernel dequantizes the bc planes of one step into one (bc*Bk, tn) bf16
# tile (again exact) and runs ONE transposed-B MXU pass against the
# gradient tile. Tiling: grid (M/bm, C/bc, N/tn), n innermost so the
# (bm, bc*Bk) output tile stays resident across the N reduction.
# ---------------------------------------------------------------------------


def _kernel_transposed(g_ref, qw_ref, sw_ref, out_ref, *, bc: int):
    """One (bm, bc*Bk) dx tile; accumulates one tn-wide slab of N."""
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Planes stack along sublanes (Bk rows each), so the concatenation is
    # tile-aligned; the products are exact in bf16 (see above).
    w = jnp.concatenate(
        [qw_ref[cc].astype(jnp.float32) * sw_ref[cc:cc + 1, :]
         for cc in range(bc)], axis=0).astype(g_ref.dtype)  # (bc*Bk, tn)
    out_ref[...] += jax.lax.dot_general(
        g_ref[...], w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def timefloats_matmul_transposed_quantized(
    g: Array, qw: Array, sw: Array,
    *,
    cfg: TFConfig,
    bm: int = 256,
    bc: int = 8,
    tn: int = 512,
    interpret: bool | None = None,
) -> Array:
    """pallas_call wrapper on padded operands (ops.py quantizes and pads).

    g (M, N) bf16 holds the quantized gradient's exact values; qw (C, Bk, N)
    int8 and sw (C, N) f32 are the stored planes. Expects M % bm == C % bc
    == N % tn == 0. Returns the padded (M, C*Bk) dx; callers slice to k_dim.
    """
    if interpret is None:
        interpret = dispatch.current().interpret
    m_dim, n_pad = g.shape
    c_chunks, blk_k, _ = qw.shape
    assert qw.shape[2] == n_pad and sw.shape == (c_chunks, n_pad), (
        g.shape, qw.shape, sw.shape)
    assert m_dim % bm == 0 and c_chunks % bc == 0 and n_pad % tn == 0

    if cfg.adc_bits is not None:
        raise ValueError("transposed reads are modeled ADC-free (DESIGN.md "
                         "§3); the ADC applies to forward reads only")

    grid = (m_dim // bm, c_chunks // bc, n_pad // tn)
    return pl.pallas_call(
        functools.partial(_kernel_transposed, bc=bc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, tn), lambda i, c, n: (i, n)),
            pl.BlockSpec((bc, blk_k, tn), lambda i, c, n: (c, 0, n)),
            pl.BlockSpec((bc, tn), lambda i, c, n: (c, n)),
        ],
        out_specs=pl.BlockSpec((bm, bc * blk_k), lambda i, c, n: (i, c)),
        out_shape=jax.ShapeDtypeStruct((m_dim, c_chunks * blk_k), jnp.float32),
        interpret=interpret,
    )(g, qw, sw)
