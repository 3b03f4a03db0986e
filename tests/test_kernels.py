"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracle
(interpret mode, which the backend dispatch picks off a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dev dependency (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

from repro.core import timefloats as tf
from repro.core.timefloats import (TFConfig, matmul_separable,
                                   quantize_input, quantize_weight)
from repro.kernels import ops, ref


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


SHAPES = [
    (1, 64, 1),
    (8, 64, 8),
    (16, 128, 32),
    (32, 100, 16),     # K not a multiple of block
    (56, 192, 24),     # M,N not multiples of tile
    (128, 512, 64),
    (256, 256, 256),   # tile-sized
    (300, 320, 270),   # everything ragged
]


@pytest.mark.parametrize("shape", SHAPES, ids=[str(s) for s in SHAPES])
def test_kernel_matches_oracle_f32(shape):
    m, k, n = shape
    kx, kw = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31))
    x = _rand(kx, (m, k))
    w = _rand(kw, (k, n))
    cfg = TFConfig(mode="separable")
    got = ops.timefloats_matmul(x, w, cfg)
    want = ref.timefloats_matmul_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16],
                         ids=["f32", "bf16", "f16"])
def test_kernel_dtype_sweep(dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(5))
    x = _rand(kx, (32, 192), dtype)
    w = _rand(kw, (192, 48), dtype)
    cfg = TFConfig(mode="separable")
    got = ops.timefloats_matmul(x, w, cfg)
    want = ref.timefloats_matmul_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert got.dtype == jnp.float32  # f32 accumulator out


@pytest.mark.parametrize("block", [32, 64, 128])
def test_kernel_block_sizes(block):
    """Crossbar height sweep incl. the ganged-crossbar 128 mode."""
    kx, kw = jax.random.split(jax.random.PRNGKey(6))
    x = _rand(kx, (48, 256))
    w = _rand(kw, (256, 32))
    cfg = TFConfig(mode="separable", block=block)
    got = ops.timefloats_matmul(x, w, cfg)
    want = ref.timefloats_matmul_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bm,bn,bc", [(8, 8, 1), (16, 32, 2), (64, 64, 4)])
def test_kernel_tile_sweep(bm, bn, bc):
    """BlockSpec tiling must not change results."""
    kx, kw = jax.random.split(jax.random.PRNGKey(7))
    x = _rand(kx, (64, 512))
    w = _rand(kw, (512, 64))
    cfg = TFConfig(mode="separable")
    got = ops.timefloats_matmul(x, w, cfg, bm=bm, bn=bn, bc=bc)
    want = ref.timefloats_matmul_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_adc_fixed_mode_bit_exact():
    """adc_mode='fixed' is supported in-kernel and must match the scan oracle
    exactly (same static full-scale)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(8))
    x = _rand(kx, (16, 128), scale=4.0)
    w = _rand(kw, (128, 16))
    cfg = TFConfig(mode="separable", adc_bits=6, adc_mode="fixed")
    got = ops.timefloats_matmul(x, w, cfg)
    want = ref.timefloats_matmul_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_dynamic_adc_rejected():
    kx, kw = jax.random.split(jax.random.PRNGKey(9))
    x = _rand(kx, (8, 64))
    w = _rand(kw, (64, 8))
    from repro.kernels.timefloats_matmul import timefloats_matmul_quantized
    cfg = TFConfig(mode="separable", adc_bits=4, adc_mode="dynamic")
    qx = quantize_input(x, cfg)
    qw = quantize_weight(w, cfg)
    with pytest.raises(ValueError, match="fixed"):
        timefloats_matmul_quantized(qx.q, qx.scale, qw.q, qw.scale, cfg=cfg,
                                    bm=8, bn=8, bc=1)


def test_quantized_entrypoint_matches():
    """ops.quantized_matmul on pre-quantized operands == full entrypoint."""
    kx, kw = jax.random.split(jax.random.PRNGKey(10))
    x = _rand(kx, (24, 192))
    w = _rand(kw, (192, 40))
    cfg = TFConfig(mode="separable")
    qx = quantize_input(x, cfg)
    qw = quantize_weight(w, cfg)
    got = ops.quantized_matmul(qx, qw, cfg=cfg)[:24, :40]
    want = ops.timefloats_matmul(x, w, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_pallas_mode_dispatch():
    """core.timefloats.matmul(mode='pallas') routes through the kernel."""
    from repro.core import timefloats as tf
    kx, kw = jax.random.split(jax.random.PRNGKey(11))
    x = _rand(kx, (16, 128))
    w = _rand(kw, (128, 16))
    got = tf.matmul(x, w, TFConfig(mode="pallas"))
    want = tf.matmul(x, w, TFConfig(mode="separable"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(deadline=None, max_examples=25)
@given(st.integers(1, 40), st.integers(1, 200), st.integers(1, 40),
       st.integers(0, 2**31 - 1))
def test_property_kernel_oracle_any_shape(m, k, n, seed):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(kx, (m, k))
    w = _rand(kw, (k, n))
    cfg = TFConfig(mode="separable")
    got = ops.timefloats_matmul(x, w, cfg)
    want = ref.timefloats_matmul_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


TRANSPOSED_SHAPES = [
    (8, 64, 64),
    (16, 100, 48),     # N not a multiple of block
    (56, 192, 300),    # K larger than one plane set, ragged M
    (3, 17, 9),        # tiny/degenerate
    (128, 256, 128),
]


@pytest.mark.parametrize("shape", TRANSPOSED_SHAPES,
                         ids=[str(s) for s in TRANSPOSED_SHAPES])
def test_transposed_kernel_matches_oracle(shape):
    """dx = g @ W^T through the transposed-read kernel == XLA oracle on the
    same stored planes (DESIGN.md §3)."""
    m, n, k = shape
    kg, kw = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31))
    g = _rand(kg, (m, n))
    w = _rand(kw, (k, n))
    cfg = TFConfig(mode="separable")
    qw = quantize_weight(w, cfg)
    got = ops.timefloats_matmul_transposed(g, qw, k_dim=k, cfg=cfg)
    want = ref.timefloats_matmul_transposed_ref(g, qw, k, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_transposed_read_roundtrip_identity():
    """Transposed-read round trip: streaming the identity through the
    transposed path must return exactly the dequantized stored planes —
    i.e. the backward pass reads precisely the codes the forward pass
    wrote, with no re-quantization anywhere on the weight side."""
    k, n = 130, 24
    w = _rand(jax.random.PRNGKey(3), (k, n))
    cfg = TFConfig(mode="separable")
    qw = quantize_weight(w, cfg)
    eye = jnp.eye(n, dtype=jnp.float32)
    got = tf.matmul_separable_transposed(eye, qw, k, cfg)      # (N, K)
    want = tf.dequantize_weight(qw, k).astype(jnp.float32).T   # (N, K)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and through the Pallas kernel
    got_k = ops.timefloats_matmul_transposed(eye, qw, k_dim=k, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want))


def test_transposed_adc_falls_back_to_xla():
    """With an ADC configured the kernel entry must route to the (ADC-free
    transposed-read) XLA reference rather than the kernel."""
    kg, kw = jax.random.split(jax.random.PRNGKey(4))
    g = _rand(kg, (8, 64))
    w = _rand(kw, (32, 64))
    cfg = TFConfig(mode="separable", adc_bits=4)
    qw = quantize_weight(w, cfg)
    got = ops.timefloats_matmul_transposed(g, qw, k_dim=32, cfg=cfg)
    want = ref.timefloats_matmul_transposed_ref(g, qw, 32, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_vjp_through_pallas_mode():
    """Training path with mode='pallas': gradients finite and descending."""
    from repro.core import timefloats as tf
    cfg = TFConfig(mode="pallas")
    kx, kw = jax.random.split(jax.random.PRNGKey(12))
    x = _rand(kx, (8, 64))
    w = _rand(kw, (64, 8))

    def loss(w):
        return jnp.sum(tf.linear(x, w, cfg) ** 2)

    l0 = float(loss(w))
    g = jax.grad(loss)(w)
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(loss(w - 1e-3 * g)) < l0
