"""Fused split-K flash-decoding over the paged KV pool (DESIGN.md §9).

The PR 5 paged decode path was gather-then-attend: ``paged_view``
materializes a dense ``(B, T*page, *feat)`` copy of every row's pages and
the attention family runs a full softmax on top — a round trip through
HBM that TimeFloats' stay-in-one-domain thesis says to avoid. This module
fuses the two: the kernel walks the per-slot page table, grid (slot,
kv-split, page-in-split). The table and lengths are scalar-prefetched
into SMEM and the pools stay in HBM; each step's block index names the
page the table holds, so the pipeline DMAs one page at a time (the same
idiom as kernels/paged.py) and VMEM holds a few pages whatever the pool
size. Each page is folded into the split's online-softmax state, and the
partial ``(m, l, acc)`` split state is reduced by a final combine:

    m* = max_s m_s,   l* = sum_s l_s * exp(m_s - m*),
    out = sum_s acc_s * exp(m_s - m*) / max(l*, eps).

Two entry points cover the serving families:

- :func:`paged_decode_attention` — GQA/MQA decode: ``q (B, H, Dk)``
  against pools ``(P, page, Hkv, Dk)/(P, page, Hkv, Dv)``.
- :func:`paged_decode_mla` — absorbed MLA decode (MQA in latent space):
  latent/rope queries against the ``(P, page, C)/(P, page, R)`` pools,
  scores = (q_lat·c_kv + q_rope·k_rope)·scale and values = c_kv.

Both have a jnp *structural reference* that softmaxes each split as one
block; the kernel folds it page by page, so the two agree to f32
reassociation — the oracle-differential gate in tests/test_paged_attn.py
holds them to that. The reference is also the production path off a TPU
(dispatch.use_pallas=False): it is leaner
than the ``paged_view``+softmax composition and, driven by the engine's
KV-extent cap (models/model.decode_step ``kv_cap``), only ever touches
the live prefix of the table instead of all ``max_len`` positions.

Masking contract: a row attends to positions ``pos < lengths[b]``
(decode append-at-end causal; ``lengths`` includes the new token).
Length-0 rows return exact zeros. Page-table entries past a row's extent
point at the trash page 0 — never mixed in (the kernel skips pages wholly
past the length; the reference masks them).

Split count: ``n_splits`` must divide the table extent; ``None`` asks
kernels/autotune for the cached per-(page, heads, head_dim) choice.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune, dispatch

Array = jax.Array
NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
_EPS = 1e-30


# ---------------------------------------------------------------------------
# Per-split block math of the jnp reference (vmapped over the batch).
# ---------------------------------------------------------------------------


def _positions(start, j: int) -> Array:
    # 2D iota then squeeze: TPU Pallas rejects 1D iota (see pallas guide).
    return start + jax.lax.broadcasted_iota(jnp.int32, (1, j), 1)[0]


def _attend_block_gqa(q, k, v, start, length, scale: float):
    """One split for one row. q (Hkv, G, Dk); k (J, Hkv, Dk);
    v (J, Hkv, Dv); all float32. Returns m, l (Hkv, G) and acc
    (Hkv, G, Dv) — unnormalized online-softmax split state."""
    j = k.shape[0]
    valid = _positions(start, j) < length                       # (J,)
    s = jnp.einsum("kgd,jkd->kgj", q, k,
                   preferred_element_type=jnp.float32) * scale  # (Hkv, G, J)
    s = jnp.where(valid[None, None, :], s, NEG)
    m = jnp.max(s, axis=-1)
    # Explicit zeroing: a fully-masked split has m == NEG, where exp(s - m)
    # would be exp(0) = 1 on every masked lane — `valid` must win, not exp.
    p = jnp.where(valid[None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("kgj,jkd->kgd", p, v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _attend_block_mla(q_lat, q_rope, ckv, kr, start, length, scale: float):
    """One MLA split for one row. q_lat (H, C); q_rope (H, R);
    ckv (J, C); kr (J, R); float32. Values are the latents themselves:
    returns m, l shaped (H,) and acc (H, C).

    Expressed THROUGH the GQA block as single-group MQA with the latent
    and rope features concatenated: scores = (q_lat·c_kv + q_rope·k_rope)
    becomes one fused dot over C+R. Besides being one gemm instead of
    two, the GQA einsum pattern carries a unit kv-head batch dim, which
    keeps XLA's lowering identical between the vmapped reference and the
    per-program kernel — the batchless "hc,jc->hj" form broke bitwise
    parity at H == 1 (gemv-specialized differently under vmap)."""
    q = jnp.concatenate([q_lat, q_rope], axis=-1)[None]   # (1, H, C+R)
    k = jnp.concatenate([ckv, kr], axis=-1)[:, None]      # (J, 1, C+R)
    v = ckv[:, None]                                      # (J, 1, C)
    m, l, acc = _attend_block_gqa(q, k, v, start, length, scale)
    return m[0], l[0], acc[0]


@jax.jit
def _combine(m: Array, l: Array, acc: Array) -> Array:
    """Reduce split state over axis 1. m, l (B, S, N); acc (B, S, N, Dv).
    All-masked rows (every split at m == NEG) come out exactly zero.

    A SEPARATE executable on purpose: the partial-producing functions are
    jitted without it and the public dispatchers call it afterwards, so at
    top level (the oracle-differential tests) the combine cannot fuse
    differently with its two producers — XLA's simplifier re-associates
    the alpha/normalize arithmetic depending on what feeds it, which was
    observed to break bitwise Pallas-vs-reference parity. Under an outer
    jit (the serving engine) the boundary dissolves and everything fuses;
    only token-level parity is promised there."""
    m_star = jnp.max(m, axis=1)                                 # (B, N)
    alpha = jnp.exp(m - m_star[:, None])                        # (B, S, N)
    l_star = jnp.sum(l * alpha, axis=1)
    acc_star = jnp.sum(acc * alpha[..., None], axis=1)
    return acc_star / jnp.maximum(l_star, _EPS)[..., None]      # (B, N, Dv)


def _norm_splits(n_splits: Optional[int], n_table: int, *, page_size: int,
                 heads: int, head_dim: int,
                 rows: Optional[int] = None) -> int:
    if n_splits is None:
        # rows = launch batch (decode: slots; speculative tree verify:
        # batch * (K+1)) — lets the autotuner's rows-qualified records
        # pick a different split for the much wider verify launches.
        n_splits = autotune.best_n_splits(page_size, heads, head_dim,
                                          rows=rows)
    n_splits = max(1, min(int(n_splits), n_table))
    while n_table % n_splits:
        n_splits -= 1  # largest divisor <= request (pow2 tables: exact)
    return n_splits


# ---------------------------------------------------------------------------
# GQA/MQA decode
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("scale", "n_splits"))
def _gqa_ref(q, k_pool, v_pool, pt, lengths, *, scale: float, n_splits: int):
    b, h, dk = q.shape
    _, page, hkv, _ = k_pool.shape
    dv = v_pool.shape[-1]
    g = h // hkv
    t = pt.shape[1]
    ts = t // n_splits
    qf = q.astype(jnp.float32).reshape(b, hkv, g, dk)
    lengths = lengths.astype(jnp.int32)
    block = jax.vmap(_attend_block_gqa,
                     in_axes=(0, 0, 0, None, 0, None))
    ms, ls, accs = [], [], []
    for s in range(n_splits):
        pts = pt[:, s * ts:(s + 1) * ts]                 # (B, ts)
        ks = k_pool[pts].astype(jnp.float32).reshape(b, ts * page, hkv, dk)
        vs = v_pool[pts].astype(jnp.float32).reshape(b, ts * page, hkv, dv)
        m, l, acc = block(qf, ks, vs, s * ts * page, lengths, scale)
        ms.append(m.reshape(b, h))
        ls.append(l.reshape(b, h))
        accs.append(acc.reshape(b, h, dv))
    return jnp.stack(ms, 1), jnp.stack(ls, 1), jnp.stack(accs, 1)


def _online_page_update(s, v_of, valid, m_ref, l_ref, acc_ref):
    """Fold one page into a split's online-softmax state (kernel side).

    s (H, page) scaled scores; ``v_of(p)`` returns p (H, page) @ values
    (H, Dv); valid (1, page). The refs hold m, l (H, 1) and acc (H, Dv)."""
    s = jnp.where(valid, s, NEG)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + v_of(p)
    m_ref[...] = m_new


def _nt(a, b):
    """a (M, D) @ b (N, D)^T in f32 — the MXU's transposed-B form."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _page_walk(body, *, ts: int, page: int):
    """Kernel prologue shared by the paged decoders: grid (row, split,
    page-in-split). Zeroes the split state on the split's first page, and
    runs ``body(start, length)`` only for pages holding live positions —
    pages past a row's extent (trash page 0) are never folded in."""

    def kernel(pt_ref, len_ref, *refs):
        del pt_ref  # consumed by the index maps
        m_ref, l_ref, acc_ref = refs[-3:]
        row, split, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        start = (split * ts + j) * page
        length = len_ref[row]

        @pl.when(start < length)
        def _fold():
            body(*refs[:-3], start, length, m_ref, l_ref, acc_ref)

    return kernel


def _paged_call(kernel, pt, lengths, operands, pools, out_dims, *, b: int,
                n_splits: int, ts: int, interpret: bool):
    """pallas_call over grid (row, split, page-in-split).

    The page table and lengths are scalar-prefetched into SMEM; each pool
    stays in HBM and the block index map DMAs exactly the page the table
    names at each step (double-buffered by the pipeline), so VMEM holds a
    few pages whatever the pool size. ``operands`` are per-row (B, H, D)
    query arrays, resident across a row's steps; ``out_dims`` are the
    feature widths of the (B, S, H, dim) split-state outputs (m, l, acc)."""
    t = pt.shape[1]
    h = operands[0].shape[1]

    def row_block(x):
        return pl.BlockSpec((pl.Squeezed(),) + x.shape[1:],
                            lambda i, s, j, pt_r, ln_r: (i, 0, 0))

    def page_block(x):
        return pl.BlockSpec(
            (pl.Squeezed(),) + x.shape[1:],
            lambda i, s, j, pt_r, ln_r: (pt_r[i * t + s * ts + j], 0, 0))

    def out_block(dim):
        return pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), h, dim),
                            lambda i, s, j, pt_r, ln_r: (i, s, 0, 0))

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_splits, ts),
            in_specs=[row_block(x) for x in operands]
            + [page_block(x) for x in pools],
            out_specs=[out_block(d) for d in out_dims]),
        out_shape=[jax.ShapeDtypeStruct((b, n_splits, h, d), jnp.float32)
                   for d in out_dims],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pt.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      *operands, *pools)
    return m[..., 0], l[..., 0], acc


def _gqa_body(hkv: int, g: int, dk: int, dv: int, page: int, scale: float):
    def body(q_ref, k_ref, v_ref, start, length, m_ref, l_ref, acc_ref):
        q = q_ref[...].astype(jnp.float32)                 # (H, Dk)
        k = k_ref[...].astype(jnp.float32)                 # (page, Hkv*Dk)
        v = v_ref[...].astype(jnp.float32)                 # (page, Hkv*Dv)
        # Query row r belongs to kv head r // g. Every kv head is scored
        # against all H rows and the rows of its group kept: 2-D dots on
        # lane-aligned head slices, no in-kernel head transpose.
        grp = jax.lax.broadcasted_iota(jnp.int32, (hkv * g, 1), 0) // g
        s = jnp.zeros((hkv * g, page), jnp.float32)
        for kh in range(hkv):
            s = jnp.where(grp == kh, _nt(q, k[:, kh * dk:(kh + 1) * dk]), s)

        def v_of(p):
            out = jnp.zeros((hkv * g, dv), jnp.float32)
            for kh in range(hkv):
                out = jnp.where(grp == kh,
                                _nn(p, v[:, kh * dv:(kh + 1) * dv]), out)
            return out

        valid = start + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1) \
            < length
        _online_page_update(s * scale, v_of, valid, m_ref, l_ref, acc_ref)

    return body


@partial(jax.jit, static_argnames=("scale", "n_splits", "interpret"))
def _gqa_pallas(q, k_pool, v_pool, pt, lengths, *, scale: float,
                n_splits: int, interpret: bool):
    b, h, dk = q.shape
    p, page, hkv, _ = k_pool.shape
    dv = v_pool.shape[-1]
    ts = pt.shape[1] // n_splits
    kernel = _page_walk(_gqa_body(hkv, h // hkv, dk, dv, page, scale),
                        ts=ts, page=page)
    return _paged_call(kernel, pt, lengths, [q],
                       [k_pool.reshape(p, page, hkv * dk),
                        v_pool.reshape(p, page, hkv * dv)],
                       (1, 1, dv), b=b, n_splits=n_splits, ts=ts,
                       interpret=interpret)


def paged_decode_attention(q: Array, k_pool: Array, v_pool: Array,
                           page_table: Array, lengths: Array, *,
                           scale: Optional[float] = None,
                           n_splits: Optional[int] = None,
                           use_pallas: Optional[bool] = None,
                           interpret: Optional[bool] = None) -> Array:
    """Fused paged GQA/MQA decode attention.

    q (B, H, Dk); k_pool (P, page, Hkv, Dk); v_pool (P, page, Hkv, Dv);
    page_table (B, T) int; lengths (B,) int (valid kv extent, incl. the
    just-written token; rows attend to ``pos < lengths[b]``). Returns
    (B, H, Dv) float32. Callers may pass a page-table *prefix* (the
    engine's KV-extent cap) as long as every row's length fits it.
    """
    d = dispatch.resolve(use_pallas, interpret)
    b, h, dk = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    ns = _norm_splits(n_splits, page_table.shape[1],
                      page_size=k_pool.shape[1], heads=h, head_dim=dk,
                      rows=b)
    fn = _gqa_pallas if d.use_pallas else _gqa_ref
    kw = {"interpret": d.interpret} if d.use_pallas else {}
    return _combine(*fn(q, k_pool, v_pool, page_table, lengths,
                        scale=float(scale), n_splits=ns, **kw))


# ---------------------------------------------------------------------------
# Absorbed-MLA decode (MQA in latent space)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("scale", "n_splits"))
def _mla_ref(q_lat, q_rope, ckv_pool, kr_pool, pt, lengths, *, scale: float,
             n_splits: int):
    b, h, c = q_lat.shape
    r = q_rope.shape[-1]
    page = ckv_pool.shape[1]
    t = pt.shape[1]
    ts = t // n_splits
    qlf = q_lat.astype(jnp.float32)
    qrf = q_rope.astype(jnp.float32)
    lengths = lengths.astype(jnp.int32)
    block = jax.vmap(_attend_block_mla,
                     in_axes=(0, 0, 0, 0, None, 0, None))
    ms, ls, accs = [], [], []
    for s in range(n_splits):
        pts = pt[:, s * ts:(s + 1) * ts]
        cs = ckv_pool[pts].astype(jnp.float32).reshape(b, ts * page, c)
        rs = kr_pool[pts].astype(jnp.float32).reshape(b, ts * page, r)
        m, l, acc = block(qlf, qrf, cs, rs, s * ts * page, lengths, scale)
        ms.append(m)
        ls.append(l)
        accs.append(acc)
    return jnp.stack(ms, 1), jnp.stack(ls, 1), jnp.stack(accs, 1)


def _mla_body(page: int, scale: float):
    def body(ql_ref, qr_ref, c_ref, r_ref, start, length, m_ref, l_ref,
             acc_ref):
        ckv = c_ref[...].astype(jnp.float32)               # (page, C)
        s = (_nt(ql_ref[...].astype(jnp.float32), ckv)
             + _nt(qr_ref[...].astype(jnp.float32),
                   r_ref[...].astype(jnp.float32)))        # (H, page)
        valid = start + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1) \
            < length
        _online_page_update(s * scale, lambda p: _nn(p, ckv), valid,
                            m_ref, l_ref, acc_ref)

    return body


@partial(jax.jit, static_argnames=("scale", "n_splits", "interpret"))
def _mla_pallas(q_lat, q_rope, ckv_pool, kr_pool, pt, lengths, *,
                scale: float, n_splits: int, interpret: bool):
    b, h, c = q_lat.shape
    page = ckv_pool.shape[1]
    ts = pt.shape[1] // n_splits
    kernel = _page_walk(_mla_body(page, scale), ts=ts, page=page)
    return _paged_call(kernel, pt, lengths, [q_lat, q_rope],
                       [ckv_pool, kr_pool], (1, 1, c), b=b,
                       n_splits=n_splits, ts=ts, interpret=interpret)


def paged_decode_mla(q_lat: Array, q_rope: Array, ckv_pool: Array,
                     kr_pool: Array, page_table: Array, lengths: Array, *,
                     scale: float,
                     n_splits: Optional[int] = None,
                     use_pallas: Optional[bool] = None,
                     interpret: Optional[bool] = None) -> Array:
    """Fused paged absorbed-MLA decode.

    q_lat (B, H, C) (queries absorbed into the latent space), q_rope
    (B, H, R); pools (P, page, C) / (P, page, R); page_table (B, T);
    lengths (B,). scores = (q_lat·c_kv + q_rope·k_rope)·scale, values are
    the c_kv latents. Returns latent attention output (B, H, C) float32
    (the caller applies W_v_b). ``scale`` is required: it depends on the
    pre-absorption head dims (nope+rope), not on C.
    """
    d = dispatch.resolve(use_pallas, interpret)
    b, h, c = q_lat.shape
    ns = _norm_splits(n_splits, page_table.shape[1],
                      page_size=ckv_pool.shape[1], heads=h,
                      head_dim=c + q_rope.shape[-1], rows=b)
    fn = _mla_pallas if d.use_pallas else _mla_ref
    kw = {"interpret": d.interpret} if d.use_pallas else {}
    return _combine(*fn(q_lat, q_rope, ckv_pool, kr_pool, page_table,
                        lengths, scale=float(scale), n_splits=ns, **kw))
