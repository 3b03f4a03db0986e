"""Bit-identity of the quantized-operand cache (DESIGN.md §3), at the level
of one linear layer.

The contract: caching changes *when* quantization happens, never *what* it
produces. Cached (quantized residuals / precomputed weight entries) and
uncached (re-quantize in the backward pass) executions must produce
bit-identical y, dx and dW in every mode; exact mode must additionally be
bit-identical to the pre-cache implementation (whose backward re-decomposed
w.T / x.T — elementwise decomposition is transpose-equivariant, so only the
separable plane layouts changed semantics, and those by design).

The MoE layer stack is checked here too, op by op with jit disabled, where
every leaf of the gradients must match bit for bit. The jitted scanned
stacks are in test_cache_stack*.py and test_cache_remat.py; serving and the
train step in test_cache_model.py.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dev dependency (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

from repro.core import timefloats as tf
from repro.core.timefloats import TFConfig
from repro.models import common

from _model_helpers import (MODES, family_batch, family_cfg,
                            loss_and_grads, mlp_model_cfg)


def _data(key=0, lead=(3, 5), k=96, n=10):
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(key), 3)
    x = jax.random.normal(kx, (*lead, k))
    w = jax.random.normal(kw, (k, n))
    g = jax.random.normal(kg, (*lead, n))
    return x, w, g


def _run(fn, x, w, g):
    """y, dx and dW of fn at (x, w) for cotangent g, as one jitted program
    (the contract is about values; eager dispatch only costs time)."""
    def fwd_bwd(x, w, g):
        y, vjp = jax.vjp(fn, x, w)
        return (y, *vjp(g))

    return tuple(np.asarray(a) for a in jax.jit(fwd_bwd)(x, w, g))


def _primal(fn, *args):
    """fn(*args) outside autodiff, jitted like _run."""
    return np.asarray(jax.jit(fn)(*args))


# prepare_weight of one weight, and of a stack of them over the leading
# dims (vmapped once per dim), jitted once per shape and mode (the stacking
# law is about values).
_prepare = jax.jit(tf.prepare_weight, static_argnums=1)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _prepare_stacked(w, cfg, stack_dims=1):
    prep = functools.partial(tf.prepare_weight, cfg=cfg)
    for _ in range(stack_dims):
        prep = jax.vmap(prep)
    return prep(w)


# First in the file: it takes minutes, and the linear tests after it keep
# the file's last tests short (a test worker is handed its next file while
# it runs a file's last two tests). Op by op, the stack runs as about 2,900
# distinct single-op programs, each compiled once: that count, not the
# batch or the number of experts, sets its time, hence the raised limit.
@pytest.mark.time_limit(900)
def test_stacked_cache_moe_bit_identical_op_by_op():
    """Op-by-op (jit disabled), cached vs uncached MoE loss AND grads are
    bit-identical on EVERY leaf — the tolerance in the jitted comparison
    covers XLA's program-dependent dot reduction order, not our math."""
    cfg_c = family_cfg("moe", "separable", cache=True)
    cfg_u = family_cfg("moe", "separable", cache=False)
    batch = family_batch(cfg_c)
    with jax.disable_jit():
        lc, gc = loss_and_grads(cfg_c, batch, jit=False)
        lu, gu = loss_and_grads(cfg_u, batch, jit=False)
    np.testing.assert_array_equal(lc, lu)
    jax.tree.map(np.testing.assert_array_equal, gc, gu)


@pytest.mark.parametrize("mode", MODES)
def test_cached_vs_uncached_bit_identical(mode):
    """fwd/dx/dW: quantized residuals == re-quantized float residuals."""
    x, w, g = _data()
    cfg_c = TFConfig(mode=mode)               # cache=True default
    cfg_u = TFConfig(mode=mode, cache=False)
    y_c, dx_c, dw_c = _run(lambda a, b: tf.linear(a, b, cfg_c), x, w, g)
    y_u, dx_u, dw_u = _run(lambda a, b: tf.linear(a, b, cfg_u), x, w, g)
    np.testing.assert_array_equal(y_c, y_u)
    np.testing.assert_array_equal(dx_c, dx_u)
    np.testing.assert_array_equal(dw_c, dw_u)


@pytest.mark.parametrize("mode", MODES)
def test_fwd_primal_matches_vjp_fwd(mode):
    """linear() outside autodiff == the custom_vjp forward (the prepared
    path must reproduce _scaled_matmul bit-for-bit)."""
    x, w, g = _data(key=1)
    cfg = TFConfig(mode=mode)
    y_p = _primal(lambda a, b: tf.linear(a, b, cfg), x, w)
    y_f, _, _ = _run(lambda a, b: tf.linear(a, b, cfg), x, w, g)
    np.testing.assert_array_equal(y_p, y_f)


@pytest.mark.parametrize("mode", MODES)
def test_weight_cache_entry_bit_identical(mode):
    """linear_cached with a precomputed prepare_weight entry == linear."""
    x, w, g = _data(key=2)
    cfg = TFConfig(mode=mode)
    pw = tf.prepare_weight(w, cfg)
    y_a, dx_a, dw_a = _run(lambda a, b: tf.linear(a, b, cfg), x, w, g)
    y_b, dx_b, dw_b = _run(
        lambda a, b: tf.linear_cached(a, b, pw, cfg), x, w, g)
    np.testing.assert_array_equal(y_a, y_b)
    np.testing.assert_array_equal(dx_a, dx_b)
    np.testing.assert_array_equal(dw_a, dw_b)


def test_exact_mode_matches_precache_backward():
    """Exact mode is the oracle: the cached backward must equal the
    pre-cache formulation (re-quantizing w.T / x.T from float32) bitwise."""
    x, w, g = _data(key=3)
    cfg = TFConfig(mode="exact")
    _, dx, dw = _run(lambda a, b: tf.linear(a, b, cfg), x, w, g)
    g2 = g.reshape(-1, g.shape[-1])
    x2 = x.reshape(-1, x.shape[-1])
    legacy_dx = _primal(lambda a, b: tf._scaled_matmul(a, b, cfg),
                        g2, w.T).reshape(x.shape)
    legacy_dw = _primal(lambda a, b: tf._scaled_matmul(a, b, cfg), x2.T, g2)
    np.testing.assert_array_equal(dx, legacy_dx)
    np.testing.assert_array_equal(dw, legacy_dw)


def test_separable_transposed_read_tracks_f32_gradients():
    """The transposed read changes the W/x-side alignment grouping vs the
    pre-cache backward (documented, DESIGN.md §3); it must stay as close to
    the f32 gradients as FP8 allows."""
    x, w, g = _data(key=4, lead=(64,), k=256, n=32)
    cfg = TFConfig(mode="separable")
    _, dx, dw = _run(lambda a, b: tf.linear(a, b, cfg), x, w, g)
    rdx, rdw = np.asarray(g @ w.T), np.asarray(x.T @ g)

    def cos(a, b):
        return float((a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b)))

    assert cos(dx, rdx) > 0.98
    assert cos(dw, rdw) > 0.98


def test_separable_pallas_backward_bit_identical():
    """separable and pallas must stay mutually bit-identical through the
    new backward (dx via the transposed kernel, dW via the shared XLA
    outer-product read)."""
    x, w, g = _data(key=5, lead=(8,), k=128, n=16)
    outs = {}
    for mode in ("separable", "pallas"):
        outs[mode] = _run(lambda a, b: tf.linear(a, b, TFConfig(mode=mode)),
                          x, w, g)
    for a, b in zip(outs["separable"], outs["pallas"]):
        np.testing.assert_array_equal(a, b)


def test_adc_training_path_runs_through_cache():
    """adc_bits forces the scanned forward; backward transposed reads are
    modeled ADC-free — the whole vjp must stay finite and cache-invariant."""
    x, w, g = _data(key=6, lead=(4,), k=64, n=8)
    outs = {}
    for cache in (True, False):
        cfg = TFConfig(mode="separable", adc_bits=4, cache=cache)
        y, dx, dw = _run(lambda a, b: tf.linear(a, b, cfg), x, w, g)
        assert np.isfinite(y).all() and np.isfinite(dx).all()
        assert np.isfinite(dw).all()
        outs[cache] = (y, dx, dw)
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# The models/common.py + train/step.py hook
# ---------------------------------------------------------------------------


def test_dense_weight_cache_scope_bit_identical():
    """common.dense under weight_cache_scope == without it, for values and
    for gradients through the params."""
    model_cfg = mlp_model_cfg()
    kx, kw = jax.random.split(jax.random.PRNGKey(7))
    d = model_cfg.d_model
    params = {"w_up": jax.random.normal(kw, (d, 2 * d))}
    x = jax.random.normal(kx, (4, d))

    def loss(p, use_cache):
        cache = common.build_weight_cache(p, model_cfg) if use_cache else None
        with common.weight_cache_scope(p, cache):
            return jnp.sum(common.dense(x, p["w_up"], model_cfg) ** 2)

    l0, g0 = jax.jit(jax.value_and_grad(lambda p: loss(p, False)))(params)
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: loss(p, True)))(params)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    np.testing.assert_array_equal(np.asarray(g0["w_up"]),
                                  np.asarray(g1["w_up"]))


def test_build_weight_cache_filters():
    """Embedding tables, norms, routers and conv kernels are excluded;
    dense projection weights are included (flat), scanned layer stacks get
    stacked entries (groups); quant='none' disables the cache."""
    model_cfg = dataclasses.replace(mlp_model_cfg(), tie_embeddings=False)
    params = {
        "embed": jnp.ones((32, 8)),
        "groups": [{"params": {
            "mixer": {"wq": jnp.ones((2, 8, 4, 4)),
                      "wo": jnp.ones((2, 4, 4, 8)),
                      "conv_x": jnp.ones((2, 4, 16))},
            "ffn": {"w_up": jnp.ones((2, 8, 16)),
                    "router": jnp.ones((2, 8, 4))},
            "norm1": {"scale": jnp.ones((2, 8))},
        }}],
        "lm_head": jnp.ones((8, 32)),
        "norm": {"scale": jnp.ones((8,))},
    }
    # only which leaves are cached and the entries' shapes are read, so
    # nothing needs quantizing
    cache = jax.eval_shape(
        lambda p: common.build_weight_cache(p, model_cfg), params)
    assert isinstance(cache, common.WeightCache)
    assert sorted(cache.flat) == ["['lm_head']"]
    assert len(cache.groups) == 1
    assert sorted(cache.groups[0]) == [
        "['ffn']['w_up']", "['mixer']['wo']", "['mixer']['wq']"]
    # every stacked entry leads with the (layers,) dim and mirrors the
    # consumer's reshape: wq (2,8,4,4) -> dense rule (8, 16); wo (2,4,4,8)
    # -> dense_in rule (16, 8)
    wq = cache.groups[0]["['mixer']['wq']"]
    wo = cache.groups[0]["['mixer']['wo']"]
    assert wq.q.q.shape[0] == 2 and wq.scale.shape == (2,)
    assert wq.q.q.shape[-1] == 16 and wo.q.q.shape[-1] == 8
    off = dataclasses.replace(model_cfg, quant="none")
    assert common.build_weight_cache(params, off) is None
    hatch = dataclasses.replace(
        model_cfg, tf=dataclasses.replace(model_cfg.tf, cache=False))
    assert common.build_weight_cache(params, hatch) is None


def test_build_weight_cache_tied_head_entry():
    """Tied-embedding configs get a transposed-read head entry keyed on the
    embed leaf (the table itself stays gather-read / uncached)."""
    model_cfg = mlp_model_cfg()
    assert model_cfg.tie_embeddings
    params = {"embed": jnp.ones((32, 8)), "norm": {"scale": jnp.ones((8,))}}
    cache = common.build_weight_cache(params, model_cfg)
    assert sorted(cache.flat) == ["['embed']"]
    pw = cache.flat["['embed']"]
    assert pw.q.q.shape[-1] == 32  # prepared for the (8, 32) transposed read


# ---------------------------------------------------------------------------
# PreparedOperand as a scan operand (the tentpole mechanism, distilled)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_prepared_operand_pytree_roundtrip(mode):
    """PreparedOperand is a registered pytree (NamedTuple): flatten/
    unflatten round-trips, and vmapped preparation yields a stack whose
    every leaf leads with the (layers,) dim."""
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 96, 8))
    cfg = TFConfig(mode=mode)
    pw = tf.prepare_weight(w[0], cfg)
    leaves, treedef = jax.tree.flatten(pw)
    assert jax.tree.unflatten(treedef, leaves)._fields == pw._fields
    stacked = jax.vmap(lambda wi: tf.prepare_weight(wi, cfg))(w)
    assert jax.tree.structure(stacked) == treedef
    for a, b in zip(jax.tree.leaves(stacked), leaves):
        assert a.shape == (3,) + b.shape


@pytest.mark.parametrize("mode", MODES)
def test_prepared_operand_scan_threading(mode):
    """A stack of prepared weights threaded through lax.scan as xs yields
    per-layer slices that reproduce tf.linear bit-for-bit — the exact
    mechanism models/model._run_groups uses."""
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    ws = jax.random.normal(kw, (3, 96, 8))
    x = jax.random.normal(kx, (4, 96))
    cfg = TFConfig(mode=mode)
    stacked = jax.vmap(lambda wi: tf.prepare_weight(wi, cfg))(ws)

    def body(carry, xs):
        w, pw = xs
        return carry, tf.linear_cached(x, w, pw, cfg)

    _, ys = jax.lax.scan(body, 0.0, (ws, stacked))
    for i in range(ws.shape[0]):
        np.testing.assert_array_equal(
            np.asarray(ys[i]), np.asarray(tf.linear(x, ws[i], cfg)))


def test_stacking_law_smoke():
    """Deterministic stacking-law check (runs even without hypothesis):
    vmap(prepare_weight) over a stack == per-layer prepare_weight of each
    slice, leaf-exact — including the double-vmap expert rule."""
    for mode in MODES:
        cfg = TFConfig(mode=mode)
        w = jax.random.normal(jax.random.PRNGKey(2), (4, 70, 6)) * 3.0
        stacked = _prepare_stacked(w, cfg)
        for i in range(4):
            per = _prepare(w[i], cfg)
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)),
                jax.tree.map(lambda a: a[i], stacked), per)
    # expert rule: (layers, E, d, f) -> vmap over layers of vmap over E
    cfg = TFConfig(mode="separable")
    we = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 64, 5))
    stacked = _prepare_stacked(we, cfg, 2)
    per = _prepare(we[1, 2], cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        jax.tree.map(lambda a: a[1, 2], stacked), per)


# (layers, k, n): a fixed set, so each shape compiles once however many
# examples draw it. It keeps the edges of the old ranges (layers 1-4,
# k 1-130, n 1-9): 1-sized dims, K one short of / one past a 64-row block,
# K spanning three blocks.
STACK_SHAPES = [(1, 1, 1), (4, 130, 9), (2, 64, 1), (3, 65, 5),
                (1, 127, 9), (4, 7, 3), (2, 129, 2)]


@settings(max_examples=25, deadline=None)
@given(mode=st.sampled_from(MODES),
       shape=st.sampled_from(STACK_SHAPES),
       scale_exp=st.integers(min_value=-6, max_value=6),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_stacking_law_property(mode, shape, scale_exp, seed):
    """Property form of the stacking law: for any stack shape / scale /
    mode, the scan-threaded slice equals what the residual-level fallback
    would have computed from the raw slice, leaf-exact."""
    layers, k, n = shape
    cfg = TFConfig(mode=mode)
    w = (jax.random.normal(jax.random.PRNGKey(seed), (layers, k, n))
         * (2.0 ** scale_exp))
    # sprinkle exact zeros: the nonzero plane must stack exactly too
    w = jnp.where(jnp.abs(w) < 0.1 * (2.0 ** scale_exp), 0.0, w)
    stacked = _prepare_stacked(w, cfg)
    i = seed % layers
    per = _prepare(w[i], cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        jax.tree.map(lambda a: a[i], stacked), per)
