"""Fused paged-attention decode kernel: the oracle-differential gate
(DESIGN.md §9).

Contracts under test:

- **Oracle differential**: the Pallas split-K kernels
  (:func:`paged_decode_attention`, :func:`paged_decode_mla`), run in
  interpret mode, match the jnp structural reference to f32 rounding
  (``assert_oracle``: the kernel folds a split page by page with online
  rescaling where the reference softmaxes the split as one block; both
  accumulate in f32, so they differ only in summation order) across page
  sizes {4, 8, 16}, head grids, split counts, ragged lengths (including
  0 and single-page), trash-page-0 tables and both pool dtypes. Length-0
  rows are exact zeros on both. Deterministic cases always run; a
  hypothesis fuzz widens the net when the optional dep is installed.
- **KV-extent cap neutrality**: slicing the page table to any prefix
  that covers every row's length does not change a single bit — the
  engine's pow2 cap schedule is therefore numerics-free.
- **Fused sampling**: the Gumbel-max restructuring in kernels/sampling
  (one masked argmax per slot, Pallas or jnp) reproduces the legacy
  vmapped `jax.random.categorical` engine sampler bitwise, greedy and
  tempered rows alike.
- **E2E greedy parity**: fused-decode paged engine token streams equal
  the PR 5 gather-then-attend paged engine's (`fused_decode=False`) on
  prefix-sharing streams for the qwen3, MLA, and MoE+MLA families (the
  PR 4 dense pin rides test_paged.py, where the fused paged engine is
  compared against the dense engine directly).
- **Launch/compile counts**: decode_and_sample stays ONE jitted launch
  per engine step; cap variants compile once each (a handful of pow2
  caps, not one per step) and a second drain adds ZERO new compiles.
- **Dispatch policy**: the backend's choice, `override()` scoping, and
  per-call kwargs compose in that priority order.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dev dependency (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

from test_paged import drain, mla_cfg, prefix_stream, small_cfg

from repro.configs import get_config, reduced_for_smoke
from repro.kernels import dispatch
from repro.kernels.paged_attn import (paged_decode_attention,
                                      paged_decode_mla)
from repro.kernels.sampling import sample_tokens
from repro.models import model as M


# ---------------------------------------------------------------------------
# Tolerance report helper — reusable by any differential test/bench that
# wants the failure to SAY what the numerics look like, not just "not equal".
# ---------------------------------------------------------------------------


def tolerance_report(got, want) -> dict:
    """Elementwise comparison summary: exact flag, mismatch count, max
    absolute and relative deviation (f64 accumulation)."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    diff = np.abs(g - w)
    rel = diff / np.maximum(np.abs(w), 1e-12)
    return {
        "exact": bool(np.array_equal(g, w)),
        "mismatched": int(np.count_nonzero(g != w)),
        "total": int(g.size),
        "max_abs": float(diff.max(initial=0.0)),
        "max_rel": float(rel.max(initial=0.0)),
    }


def assert_bitwise(got, want, label: str = "") -> None:
    rep = tolerance_report(got, want)
    assert rep["exact"], f"{label} not bitwise: {rep}"


# Kernel vs structural reference: the same f32 arithmetic summed in another
# order (page-by-page online softmax vs one block per split). A few ulp of
# f32 (2^-23 ~ 1.2e-7) per accumulated page bounds the gap on these O(1)
# outputs; observed max_abs ~2.4e-7.
ORACLE_RTOL, ORACLE_ATOL = 1e-5, 1e-6


def assert_oracle(got, want, label: str = "") -> None:
    rep = tolerance_report(got, want)
    ok = np.allclose(np.asarray(got), np.asarray(want), rtol=ORACLE_RTOL,
                     atol=ORACLE_ATOL)
    assert ok, f"{label} outside f32 reassociation tolerance: {rep}"


# ---------------------------------------------------------------------------
# Case construction: contiguous per-row page runs + trash/duplicate entries
# past each row's extent, ragged lengths with the edge rows pinned.
# ---------------------------------------------------------------------------


def _page_table(rng, b: int, t: int, n_pages: int) -> np.ndarray:
    pt = np.zeros((b, t), np.int32)
    ids = rng.permutation(np.arange(1, n_pages))[: b * t]
    pt.flat[: len(ids)] = ids
    return pt


def _gqa_case(rng, b, t, page, hkv, g, dk, dv, dtype):
    n_pages = b * t + 2
    q = jnp.asarray(rng.standard_normal((b, hkv * g, dk)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, page, hkv, dk)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages, page, hkv, dv)), dtype)
    lens = rng.integers(0, t * page + 1, b)
    lens[0] = 0                      # edge: empty row (exact-zero output)
    if b > 1:
        lens[1] = min(page, t * page)  # edge: single-page extent
    pt = _page_table(rng, b, t, n_pages)
    # Entries past a row's live extent point at trash page 0 — loaded but
    # masked, exactly the engine's freed-slot/teardown shape.
    for i in range(b):
        pt[i, (lens[i] + page - 1) // page:] = 0
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lens, jnp.int32)


def _mla_case(rng, b, t, page, h, c, r, dtype):
    n_pages = b * t + 2
    ql = jnp.asarray(rng.standard_normal((b, h, c)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((b, h, r)), jnp.float32)
    cp = jnp.asarray(rng.standard_normal((n_pages, page, c)), dtype)
    rp = jnp.asarray(rng.standard_normal((n_pages, page, r)), dtype)
    lens = rng.integers(0, t * page + 1, b)
    lens[0] = 0
    pt = _page_table(rng, b, t, n_pages)
    for i in range(b):
        pt[i, (lens[i] + page - 1) // page:] = 0
    return ql, qr, cp, rp, jnp.asarray(pt), jnp.asarray(lens, jnp.int32)


GQA_CASES = [
    # (b, t, page, hkv, g, dk, dv, dtype, n_splits)
    (2, 4, 8, 2, 2, 16, 16, "float32", 4),
    (1, 1, 4, 1, 1, 8, 8, "float32", 1),      # single-page table
    (3, 2, 16, 1, 4, 32, 16, "bfloat16", 2),  # MQA grouped heads
    (2, 8, 4, 4, 1, 16, 32, "bfloat16", 8),   # max splits
]

MLA_CASES = [
    # (b, t, page, h, c, r, dtype, n_splits)
    (2, 4, 8, 8, 16, 8, "float32", 4),
    (1, 1, 4, 2, 8, 4, "bfloat16", 1),
    (2, 8, 16, 4, 32, 16, "bfloat16", 8),
]


@pytest.mark.parametrize("seed,case", list(enumerate(GQA_CASES)))
def test_gqa_kernel_matches_oracle_bitwise(seed, case):
    """Pallas split-K GQA decode (interpret) == jnp reference to f32
    reassociation; empty rows exactly zero on both."""
    b, t, page, hkv, g, dk, dv, dtype, ns = case
    rng = np.random.default_rng(seed)
    q, kp, vp, pt, lens = _gqa_case(rng, b, t, page, hkv, g, dk, dv, dtype)
    want = paged_decode_attention(q, kp, vp, pt, lens, n_splits=ns,
                                  use_pallas=False)
    got = paged_decode_attention(q, kp, vp, pt, lens, n_splits=ns,
                                 use_pallas=True, interpret=True)
    assert_oracle(got, want, f"gqa{case}")
    empty = np.asarray(lens) == 0
    assert np.all(np.asarray(want)[empty] == 0.0)
    assert np.all(np.asarray(got)[empty] == 0.0)


@pytest.mark.parametrize("seed,case", list(enumerate(MLA_CASES)))
def test_mla_kernel_matches_oracle_bitwise(seed, case):
    """Pallas split-K absorbed-MLA decode (interpret) == jnp ref to f32
    reassociation; empty rows exactly zero on both."""
    b, t, page, h, c, r, dtype, ns = case
    rng = np.random.default_rng(seed)
    ql, qr, cp, rp, pt, lens = _mla_case(rng, b, t, page, h, c, r, dtype)
    want = paged_decode_mla(ql, qr, cp, rp, pt, lens, scale=0.125,
                            n_splits=ns, use_pallas=False)
    got = paged_decode_mla(ql, qr, cp, rp, pt, lens, scale=0.125,
                           n_splits=ns, use_pallas=True, interpret=True)
    assert_oracle(got, want, f"mla{case}")
    empty = np.asarray(lens) == 0
    assert np.all(np.asarray(want)[empty] == 0.0)
    assert np.all(np.asarray(got)[empty] == 0.0)


def test_gqa_oracle_matches_dense_softmax():
    """The structural reference itself is semantically right: against a
    plain dense gather+softmax (different algorithm, so tolerance, with
    the report saying how far off)."""
    rng = np.random.default_rng(3)
    q, kp, vp, pt, lens = _gqa_case(rng, 3, 4, 8, 2, 2, 16, 16, "float32")
    got = paged_decode_attention(q, kp, vp, pt, lens, n_splits=4,
                                 use_pallas=False)
    b, h, dk = q.shape
    hkv = kp.shape[2]
    k = kp[pt].reshape(b, -1, hkv, dk)
    v = vp[pt].reshape(b, -1, hkv, vp.shape[-1])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    s = jnp.einsum("bhd,bjhd->bhj", q, k) / np.sqrt(dk)
    mask = jnp.arange(k.shape[1])[None] < lens[:, None]
    s = jnp.where(mask[:, None], s, -jnp.inf)
    p = jnp.where(mask[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    want = jnp.einsum("bhj,bjhd->bhd", p, v)
    rep = tolerance_report(got, want)
    assert rep["max_abs"] < 1e-5, rep


def test_kv_cap_is_bitwise_neutral():
    """Slicing the table to any prefix covering every row's length leaves
    the output bit-identical — the engine's pow2 cap schedule is free."""
    rng = np.random.default_rng(11)
    q, kp, vp, pt, lens = _gqa_case(rng, 2, 8, 4, 2, 2, 16, 16, "float32")
    lens = jnp.minimum(lens, 4 * 4)  # live extent fits 4 of 8 pages
    full = paged_decode_attention(q, kp, vp, pt, lens, n_splits=2,
                                  use_pallas=False)
    for t_cap in (4, 8):
        capped = paged_decode_attention(q, kp, vp, pt[:, :t_cap], lens,
                                        n_splits=2, use_pallas=False)
        assert_bitwise(capped, full, f"kv_cap[{t_cap}]")


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_gqa_kernel_oracle_fuzz(data):
    """Property fuzz (hypothesis): random shape/dtype/split/ragged-length
    draws, Pallas-interpret vs reference (``assert_oracle``)."""
    b = data.draw(st.integers(1, 3), label="b")
    t = data.draw(st.sampled_from([1, 2, 4, 8]), label="t")
    page = data.draw(st.sampled_from([4, 8, 16]), label="page")
    hkv = data.draw(st.sampled_from([1, 2, 4]), label="hkv")
    g = data.draw(st.sampled_from([1, 2, 4]), label="g")
    dk = data.draw(st.sampled_from([8, 16, 32]), label="dk")
    dv = data.draw(st.sampled_from([8, 16, 32]), label="dv")
    dtype = data.draw(st.sampled_from(["float32", "bfloat16"]), label="dt")
    ns = data.draw(st.sampled_from([1, 2, 4, 8]), label="ns")
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    rng = np.random.default_rng(seed)
    q, kp, vp, pt, lens = _gqa_case(rng, b, t, page, hkv, g, dk, dv, dtype)
    want = paged_decode_attention(q, kp, vp, pt, lens, n_splits=ns,
                                  use_pallas=False)
    got = paged_decode_attention(q, kp, vp, pt, lens, n_splits=ns,
                                 use_pallas=True, interpret=True)
    assert_oracle(got, want)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_mla_kernel_oracle_fuzz(data):
    b = data.draw(st.integers(1, 3), label="b")
    t = data.draw(st.sampled_from([1, 2, 4, 8]), label="t")
    page = data.draw(st.sampled_from([4, 8, 16]), label="page")
    h = data.draw(st.sampled_from([1, 2, 8]), label="h")
    c = data.draw(st.sampled_from([8, 16, 32]), label="c")
    r = data.draw(st.sampled_from([4, 8, 16]), label="r")
    dtype = data.draw(st.sampled_from(["float32", "bfloat16"]), label="dt")
    ns = data.draw(st.sampled_from([1, 2, 4, 8]), label="ns")
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    rng = np.random.default_rng(seed)
    ql, qr, cp, rp, pt, lens = _mla_case(rng, b, t, page, h, c, r, dtype)
    want = paged_decode_mla(ql, qr, cp, rp, pt, lens, scale=0.125,
                            n_splits=ns, use_pallas=False)
    got = paged_decode_mla(ql, qr, cp, rp, pt, lens, scale=0.125,
                           n_splits=ns, use_pallas=True, interpret=True)
    assert_oracle(got, want)


# ---------------------------------------------------------------------------
# Fused sampling vs the legacy engine sampler.
# ---------------------------------------------------------------------------


def _legacy_sample(logits, temps, key, tags, counters):
    """The pre-PR 6 engine sampler, verbatim (vmapped categorical)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temps, 1e-6)
    slots_iota = jnp.arange(logits.shape[0], dtype=jnp.int32)

    def one(lg, t, slot, tag, c):
        k = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(key, slot), tag), c)
        return jax.random.categorical(k, lg / t, axis=-1)

    sampled = jax.vmap(one)(logits.astype(jnp.float32), safe_t, slots_iota,
                            tags, counters).astype(jnp.int32)
    use = temps > 0.0
    if greedy.ndim == 2:
        use = use[:, None]
    return jnp.where(use, sampled, greedy)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_sampling_matches_legacy(use_pallas):
    """Gumbel-max fused sampler (jnp and Pallas-interpret) == legacy
    vmapped-categorical sampler, bitwise, greedy and tempered rows."""
    rng = np.random.default_rng(5)
    key = jax.random.PRNGKey(9)
    lg = jnp.asarray(rng.standard_normal((6, 37)), jnp.float32)
    temps = jnp.asarray([0.0, 0.7, 1.0, 0.0, 1.3, 0.2], jnp.float32)
    tags = jnp.asarray([3, 3, 7, 1, 1, 2], jnp.int32)
    counters = jnp.asarray([0, 5, 5, 2, 0, 9], jnp.int32)
    want = _legacy_sample(lg, temps, key, tags, counters)
    got = sample_tokens(lg, temps, key, tags, counters,
                        use_pallas=use_pallas, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_sampling_audio_path_matches_legacy():
    rng = np.random.default_rng(6)
    key = jax.random.PRNGKey(2)
    lg = jnp.asarray(rng.standard_normal((3, 2, 17)), jnp.float32)
    temps = jnp.asarray([0.0, 0.9, 1.1], jnp.float32)
    tags = jnp.asarray([1, 2, 3], jnp.int32)
    counters = jnp.asarray([0, 1, 2], jnp.int32)
    want = _legacy_sample(lg, temps, key, tags, counters)
    got = sample_tokens(lg, temps, key, tags, counters)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Dispatch policy.
# ---------------------------------------------------------------------------


def test_dispatch_priority(monkeypatch):
    """backend < override < per-call kwargs, and override scoping restores.
    Off a TPU the references run and Pallas interprets; on a TPU every
    kernel runs compiled."""
    assert dispatch.current() == dispatch.KernelDispatch(False, True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dispatch.current() == dispatch.KernelDispatch(True, False)
    with dispatch.override(use_pallas=False):
        assert dispatch.current() == dispatch.KernelDispatch(False, False)
        with dispatch.override(interpret=True):
            assert dispatch.current() == dispatch.KernelDispatch(False, True)
        assert dispatch.resolve(use_pallas=True).use_pallas  # kwarg wins
    assert dispatch.current() == dispatch.KernelDispatch(True, False)


# ---------------------------------------------------------------------------
# E2E: fused engine vs the PR 5 gather-then-attend engine, and the launch /
# compile-count contract.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["attention", "mla", "moe_mla"])
def test_fused_engine_matches_gather_engine_greedy(family):
    """Greedy token streams: paged engine with the fused split-K decode
    kernel == the same engine with ``fused_decode=False`` (the PR 5
    gather+softmax path). With test_paged.py's fused-paged-vs-dense pin
    this closes the three-way PR4/PR5/PR6 parity chain per family."""
    if family == "attention":
        cfg = small_cfg()
    elif family == "mla":
        cfg = mla_cfg()
    else:
        cfg = reduced_for_smoke(get_config("deepseek-v3-671b"))
        cfg = dataclasses.replace(cfg, quant="none", n_layers=2)
    params = M.init(cfg, jax.random.PRNGKey(0))
    reqs = prefix_stream(cfg, n=4)
    _, want = drain(params, cfg, reqs, paged=True, page_size=8,
                    fused_decode=False)
    eng, got = drain(params, cfg, reqs, paged=True, page_size=8)
    assert eng.fused_decode
    assert sorted(want) == sorted(got)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])


def test_decode_is_one_launch_per_step_and_compiles_stay_flat():
    """decode_and_sample: exactly ONE jitted launch per engine step; cap
    variants compile once each; a second identical drain adds ZERO new
    compiles and ZERO new prefill buckets."""
    cfg = small_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng, done = drain(params, cfg, prefix_stream(cfg, n=4), paged=True,
                      page_size=8)
    assert len(done) == 4
    assert eng.decode_launches == eng.steps
    stats = eng.compile_cache_stats()
    assert stats["decode_total"] >= 1
    assert any(k.startswith("decode_and_sample[c") for k in stats)

    def resubmit():
        for r in prefix_stream(cfg, n=4):
            eng.submit(dataclasses.replace(r, generated=[],
                                           prompt=r.prompt.copy()))
        eng.run_until_drained()

    # Second drain warms the radix-hit suffix buckets (prefix reuse makes
    # the suffixes SHORTER than the cold drain's, a new bucket is fair
    # game); decode cap variants must already be saturated.
    resubmit()
    assert eng.compile_cache_stats()["decode_total"] == stats["decode_total"]
    warm = eng.compile_cache_stats()
    # Third drain: fully steady state — ZERO new compiles anywhere.
    resubmit()
    assert eng.decode_launches == eng.steps
    assert eng.compile_cache_stats() == warm
