"""Tests for the 5-step TimeFloats scalar product and its matmul modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dev dependency (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

from repro.core import float8, timefloats as tf
from repro.core.timefloats import DEFAULT, NoiseParams, TFConfig


def _rand(key, shape, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


# The matmul forms under test, jitted once each (cfg is static): the tests
# below are about values, and eager dispatch only costs time.
_matmul = jax.jit(tf.matmul, static_argnums=2)
_scaled_matmul = jax.jit(tf._scaled_matmul, static_argnums=2)
_matmul_exact = jax.jit(tf.matmul_exact, static_argnums=2,
                        static_argnames="noise")
_matmul_separable = jax.jit(tf.matmul_separable, static_argnums=2)
_matmul_separable_scan = jax.jit(tf.matmul_separable_scan, static_argnums=2)


# ---------------------------------------------------------------------------
# The 5 steps against a direct FP8 reference
# ---------------------------------------------------------------------------


def fp8_dot_reference(x, w, cfg: TFConfig):
    """Dot of FP8-quantized values in f64 — what the chunk output would be
    with unlimited MAC precision and no alignment truncation."""
    xq = np.asarray(float8.quantize(x, cfg.fmt), np.float64)
    wq = np.asarray(float8.quantize(w, cfg.fmt), np.float64)
    return float(np.dot(xq, wq))


@pytest.mark.parametrize("k", [1, 3, 64, 65, 200])
def test_scalar_product_close_to_fp8_reference(k):
    key = jax.random.PRNGKey(k)
    kx, kw = jax.random.split(key)
    x = _rand(kx, (k,))
    w = _rand(kw, (k,))
    got = float(tf.scalar_product_steps(x, w, DEFAULT))
    ref = fp8_dot_reference(x, w, DEFAULT)
    # alignment truncation loses at most ~2^-m per aligned term
    scale = np.sum(np.abs(np.asarray(x)) * np.abs(np.asarray(w))) + 1e-9
    assert abs(got - ref) / scale < 2.0 ** (-DEFAULT.fmt.man_bits) * 1.5


def test_exact_matches_stepwise():
    """matmul_exact must be the vectorization of scalar_product_steps."""
    key = jax.random.PRNGKey(0)
    x = _rand(key, (5, 130))
    w = _rand(jax.random.PRNGKey(1), (130, 7))
    full = _matmul_exact(x, w, DEFAULT)
    for i in [0, 2, 4]:
        for j in [0, 3, 6]:
            one = tf.scalar_product_steps(x[i], w[:, j], DEFAULT)
            np.testing.assert_allclose(float(full[i, j]), float(one),
                                       rtol=1e-6, atol=1e-7)


def test_zero_vectors():
    x = jnp.zeros((4, 64))
    w = jnp.zeros((64, 4))
    for mode in ["exact", "separable"]:
        y = _matmul(x, w, TFConfig(mode=mode))
        np.testing.assert_array_equal(np.asarray(y), 0.0)


def test_single_nonzero_element():
    """One hot row x one hot col: product must be the FP8 product exactly."""
    x = jnp.zeros((1, 64)).at[0, 17].set(1.5)
    w = jnp.zeros((64, 1)).at[17, 0].set(-0.75)
    for mode in ["exact", "separable"]:
        y = float(_matmul(x, w, TFConfig(mode=mode))[0, 0])
        assert y == pytest.approx(1.5 * -0.75, rel=2 ** -4)


@pytest.mark.parametrize("mode", ["exact", "separable"])
@pytest.mark.parametrize("shape", [(1, 1, 1), (3, 64, 5), (8, 100, 16),
                                   (16, 256, 8), (2, 500, 3)])
def test_matmul_relative_error(mode, shape):
    m, k, n = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    kx, kw = jax.random.split(key)
    x = _rand(kx, (m, k))
    w = _rand(kw, (k, n))
    y = _scaled_matmul(x, w, TFConfig(mode=mode))
    ref = x @ w
    rel = float(jnp.linalg.norm(y - ref) / (jnp.linalg.norm(ref) + 1e-9))
    # E4M4 quantization of both operands: ~6-12% relative error at these K
    assert rel < 0.25, (mode, shape, rel)


def test_exact_vs_separable_gap():
    """DESIGN.md §2: separable (per-operand) alignment is *slightly more
    accurate* than the paper's joint alignment on gaussian data — the total
    shift is split between operands instead of all landing on the input
    mantissa. (Refuted initial hypothesis 'joint is strictly better';
    recorded in EXPERIMENTS.md.)"""
    key = jax.random.PRNGKey(42)
    x = _rand(key, (32, 200))
    w = _rand(jax.random.PRNGKey(43), (200, 32))
    ref = x @ w

    def rel(mode):
        y = _scaled_matmul(x, w, TFConfig(mode=mode))
        return float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))

    r_exact, r_sep = rel("exact"), rel("separable")
    assert r_sep < r_exact * 1.1, (r_exact, r_sep)
    # both are within FP8 expectations
    assert r_exact < 0.15 and r_sep < 0.15


def test_shift_truncation_sparsity():
    """Wide dynamic range -> most terms shifted out (the paper's 'enhanced
    sparsity'); uniform-magnitude data -> almost none."""
    key = jax.random.PRNGKey(7)
    wide = _rand(key, (4, 128)) * jnp.exp2(
        jax.random.randint(jax.random.PRNGKey(8), (4, 128), -6, 7).astype(jnp.float32))
    w = _rand(jax.random.PRNGKey(9), (128, 4))
    s_wide = float(tf.expected_sparsity(wide, w, DEFAULT))
    flat = _rand(jax.random.PRNGKey(10), (4, 128))
    s_flat = float(tf.expected_sparsity(flat, w, DEFAULT))
    assert s_wide > s_flat
    assert s_wide > 0.2


def test_adc_quantization_modes():
    key = jax.random.PRNGKey(11)
    x = _rand(key, (8, 64))
    w = _rand(jax.random.PRNGKey(12), (64, 8))
    clean = _matmul(x, w, TFConfig(mode="separable"))
    dyn = _matmul(x, w, TFConfig(mode="separable", adc_bits=4))
    fixed = _matmul(x, w, TFConfig(mode="separable", adc_bits=4,
                                   adc_mode="fixed"))
    # ADC quantization adds error; dynamic ranging adds less than fixed
    e_dyn = float(jnp.linalg.norm(dyn - clean))
    e_fix = float(jnp.linalg.norm(fixed - clean))
    assert e_dyn > 0.0 and e_fix > 0.0
    assert e_dyn <= e_fix * 1.05
    # 8-bit ADC nearly transparent vs 4-bit
    fine = _matmul(x, w, TFConfig(mode="separable", adc_bits=8))
    assert float(jnp.linalg.norm(fine - clean)) < e_dyn


def test_variability_noise_paths():
    """Fig 7 mechanism: exponent noise hurts far more than mantissa noise."""
    key = jax.random.PRNGKey(13)
    x = _rand(key, (16, 128))
    w = _rand(jax.random.PRNGKey(14), (128, 16))
    clean = _matmul_exact(x, w, DEFAULT)

    def err(noise):
        noisy = _matmul_exact(x, w, DEFAULT, noise=noise,
                              key=jax.random.PRNGKey(15))
        return float(jnp.linalg.norm(noisy - clean) / jnp.linalg.norm(clean))

    e_exp = err(NoiseParams(sigma_exp=0.05))
    e_man = err(NoiseParams(sigma_mant=0.05))
    assert e_exp > 3 * e_man, (e_exp, e_man)


def test_linear_vjp_shapes_and_direction():
    """custom_vjp: grads flow through TimeFloats fwd+bwd and descend."""
    cfg = TFConfig(mode="separable")
    key = jax.random.PRNGKey(16)
    x = _rand(key, (4, 6, 32))  # leading batch dims
    w = _rand(jax.random.PRNGKey(17), (32, 8))
    y_t = _rand(jax.random.PRNGKey(18), (4, 6, 8))

    def loss(w):
        return jnp.mean((tf.linear(x, w, cfg) - y_t) ** 2)

    l0 = loss(w)
    g = jax.grad(loss)(w)
    assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g)))
    l1 = loss(w - 0.05 * g)
    assert float(l1) < float(l0)
    # grad direction agrees with the float32 gradient
    g_ref = jax.grad(lambda w: jnp.mean((x @ w - y_t) ** 2))(w)
    cos = jnp.sum(g * g_ref) / (jnp.linalg.norm(g) * jnp.linalg.norm(g_ref))
    assert float(cos) > 0.9


def test_pow2_prescale_exactness():
    """Power-of-two prescaling must be lossless for FP8 (only moves the
    exponent reference): descaled output of scaled operands == direct."""
    cfg = TFConfig(mode="separable")
    key = jax.random.PRNGKey(19)
    x = _rand(key, (8, 64)) * 1e-3   # deep under the E4M4 range
    w = _rand(jax.random.PRNGKey(20), (64, 8)) * 1e2
    y = _scaled_matmul(x, w, cfg)
    ref = x @ w
    rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
    assert rel < 0.15  # without prescale, x would flush to zero entirely
    un = _matmul(x, w, cfg)
    assert float(jnp.linalg.norm(un)) == 0.0  # proves the flush happens


# (m, k, n): a fixed set, so each shape compiles once however many examples
# draw it. It keeps the edges of the old ranges (m 1-8, k 1-130, n 1-8):
# 1-sized dims, K one short of / one past a 64-row block, ragged M/N, and
# K spanning three blocks.
SCAN_SHAPES = [(1, 1, 1), (1, 64, 8), (8, 1, 1), (3, 65, 5), (5, 127, 2),
               (8, 130, 8), (2, 33, 7), (8, 128, 1)]


@settings(deadline=None, max_examples=30)
@given(st.sampled_from(SCAN_SHAPES), st.integers(0, 2**31 - 1))
def test_property_separable_scan_equals_dense(shape, seed):
    """The scanned int8-MAC form == the one-dot dequantized form (bitwise up
    to f32 summation order) for any shape."""
    m, k, n = shape
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = _rand(kx, (m, k))
    w = _rand(kw, (k, n))
    a = _matmul_separable_scan(x, w, DEFAULT)
    b = _matmul_separable(x, w, DEFAULT)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@settings(deadline=None, max_examples=30)
@given(st.integers(0, 2**31 - 1))
def test_property_exact_upper_bounds_truncation(seed):
    """Exact-mode error vs unlimited-precision FP8 dot is bounded by the
    per-term alignment truncation: a term right-shifted by d loses
    < 2^d integer-significand units = 2^(d-m) of its own leading magnitude
    (and at most its entire value when shifted out)."""
    cfg = DEFAULT
    mb = cfg.fmt.man_bits
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    k = 64
    x = _rand(kx, (k,))
    w = _rand(kw, (k,))
    got = float(tf.scalar_product_steps(x, w, cfg))
    ref = fp8_dot_reference(x, w, cfg)

    fx = float8.decompose(x, cfg.fmt)
    fw = float8.decompose(w, cfg.fmt)
    valid = np.asarray(fx.nonzero & fw.nonzero)
    s = np.asarray(fx.exp, np.int64) + np.asarray(fw.exp, np.int64)
    e_max = s[valid].max() if valid.any() else 0
    d = np.clip(e_max - s, 0, 60)
    xq = np.abs(np.asarray(float8.quantize(x, cfg.fmt), np.float64))
    wq = np.abs(np.asarray(float8.quantize(w, cfg.fmt), np.float64))
    per_term = np.minimum(1.0, 2.0 ** (d - mb)) * xq * wq
    bound = np.sum(per_term[valid]) if valid.any() else 0.0
    assert abs(got - ref) <= bound + 1e-9, (got, ref, bound)


def test_block128_ganged_crossbar_mode():
    """block=128 (beyond-paper MXU-filling knob) stays accurate."""
    key = jax.random.PRNGKey(23)
    x = _rand(key, (16, 256))
    w = _rand(jax.random.PRNGKey(24), (256, 16))
    ref = x @ w
    for blk in (64, 128):
        y = _scaled_matmul(x, w, TFConfig(mode="separable", block=blk))
        rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
        assert rel < 0.15, (blk, rel)
