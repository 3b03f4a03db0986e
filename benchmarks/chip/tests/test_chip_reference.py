"""The plain reference's arithmetic on values worked out by hand: E4M4
rounding (ties to even, the carry into the next binade, values flushed
under the exponent range, signs), the control's E4M3, the scalar
product's gradient, and the norms and rotations around it."""
from __future__ import annotations

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the program and the benchmark on the path)
from benchmarks.chip import reference


def _alone(x: float, man_bits: int) -> float:
    """The value ``x`` stands for, alone in its block of 64, in an operand
    whose largest magnitude is 1.0 (so the prescale is 2^7)."""
    a = np.zeros((1, 128), np.float32)
    a[0, 0], a[0, 64] = 1.0, x
    vals, s = reference.aligned(a, 1, man_bits)
    return float(np.asarray(vals)[0, 64] / float(s))


@pytest.mark.parametrize("x, man_bits, want", [
    (1.0 + 1 / 32, 4, 1.0),              # a tie rounds to the even 1.0
    (1.0 + 3 / 32, 4, 1.125),            # a tie rounds to the even 1.125
    (1.0 + 1 / 16, 4, 1.0625),           # exact with four mantissa bits
    (1.0 + 1 / 16, 3, 1.0),              # a tie with three: E4M3 control
    (0.999, 4, 1.0),                     # rounds up into the next binade
    (-0.75, 4, -0.75),                   # the sign is kept
    (2.0 ** -14, 4, 2.0 ** -14),         # the smallest binade kept
    (2.0 ** -15, 4, 0.0),                # under the range: flushed
], ids=["tie-even-down", "tie-even-up", "exact", "e4m3-tie", "carry",
        "sign", "smallest", "flushed"])
def test_e4m4_rounding(x, man_bits, want):
    assert _alone(x, man_bits) == want


def test_scalar_product_gradient_reads_aligned_operands():
    import jax

    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 64)).astype(np.float32)
    w = rng.normal(size=(64, 64)).astype(np.float32)
    g = rng.normal(size=(64, 64)).astype(np.float32)
    _, vjp = jax.vjp(lambda a, b: reference.tf_matmul(a, b), x, w)
    dx, dw = vjp(g)
    xa, sx = reference.aligned(x, 1)
    wa, sw = reference.aligned(w, 0)
    g_n, sg = reference.aligned(g, 1)
    g_m, _ = reference.aligned(g, 0)
    want_dx = np.asarray(g_n, np.float64) @ np.asarray(wa, np.float64).T
    want_dw = np.asarray(xa, np.float64).T @ np.asarray(g_m, np.float64)
    np.testing.assert_allclose(np.asarray(dx), want_dx / (float(sg) * float(sw)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), want_dw / (float(sx) * float(sg)),
                               rtol=1e-5, atol=1e-6)


def test_rms_norm_gives_unit_rms():
    x = np.random.default_rng(2).normal(size=(3, 16)).astype(np.float32) * 5
    y = np.asarray(reference.rms_norm(x, np.ones(16, np.float32), 1e-6))
    np.testing.assert_allclose(np.sqrt(np.mean(y * y, -1)), 1.0, rtol=1e-5)


def test_rope_keeps_each_pair_norm_and_position_zero():
    x = np.random.default_rng(3).normal(size=(5, 2, 8)).astype(np.float32)
    y = np.asarray(reference.rope(x, np.arange(5), 10000.0))
    pair = lambda a: a[..., :4] ** 2 + a[..., 4:] ** 2
    np.testing.assert_allclose(pair(y), pair(x), rtol=1e-5)
    np.testing.assert_allclose(y[0], x[0], rtol=1e-6)
