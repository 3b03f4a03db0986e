"""The two-pass block aligner (core/timefloats.block_align, DESIGN.md §2):
its values and prescale are bit-identical to the int8-plane composition
dequantize_*(quantize_*(_pow2_prescale(x))) in both layouts, the Pallas
pass 2 (kernels/block_align.py, interpret mode here) is bit-identical to
the jnp form, and a separable train step that reads the values equals the
plane path and builds no planes. Comparisons run jitted, on fixed shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import float8
from repro.core import timefloats as tf
from repro.core.timefloats import TFConfig
from repro.kernels import dispatch
from repro.kernels.block_align import block_align_pallas

from _model_helpers import family_batch, family_cfg, loss_and_grads

CFG = TFConfig(mode="separable")


def _data(pattern, shape, dtype, seed=0):
    """A fixed-shape operand. ``wide``: rows scaled over ~2^±15 with 5%
    zeros; ``zeros``: all zero; ``signed_zeros``: whole zero and -0.0
    blocks and rows among ordinary values; ``flush``: one large value, the
    rest far enough below it to flush under the prescale."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, shape, jnp.float32)
    if pattern == "wide":
        x = x * jnp.exp2(jnp.round(jax.random.normal(k2, (shape[0], 1)) * 5))
        x = jnp.where(jax.random.uniform(k3, shape) < 0.05, 0.0, x)
    elif pattern == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif pattern == "signed_zeros":
        x = x.at[:, :64].set(0.0).at[:64, :].set(-0.0)
        x = x.at[3, 70:].set(-0.0).at[70:, 5].set(0.0)
    elif pattern == "flush":
        x = x * 2.0 ** -12
        x = x.at[1, 1].set(3.0).at[2, :].multiply(2.0 ** 9)
    return x.astype(dtype)


def _bits(a):
    return jax.lax.bitcast_convert_type(a, jnp.uint16)


@jax.jit
def _round_matches_codec(v):
    return jnp.all(tf.round_to_fmt(v, CFG.fmt) == float8.quantize(v, CFG.fmt))


@jax.jit
def _single_matches_planes(x):
    """Both layouts of block_align against the plane composition."""
    xs, s = tf._pow2_prescale(x, CFG)
    want1 = tf.dequantize_input(tf.quantize_input(xs, CFG), x.shape[1])
    want0 = tf.dequantize_weight(tf.quantize_weight(xs, CFG), x.shape[0])
    (v1,), s1 = tf.block_align(x, CFG, (1,))
    (v0,), s0 = tf.block_align(x, CFG, (0,))
    (b1, b0), sb = tf.block_align(x, CFG, (1, 0))
    same = [jnp.all(_bits(a) == _bits(b)) for a, b in
            ((v1, want1), (v0, want0), (b1, want1), (b0, want0))]
    return jnp.stack(same + [s1 == s, s0 == s, sb == s])


def test_round_to_fmt_matches_codec():
    """The bitwise E4M4 rounding equals float8.quantize across ties,
    carries into the exponent, flushes below 2^-bias and saturation."""
    m = np.arange(1 << 12, dtype=np.float32) / (1 << 12)  # fine mantissas
    e = np.exp2(np.arange(-12, 12, dtype=np.float32))
    v = (1.0 + m[:, None]) * e[None, :]
    assert bool(_round_matches_codec(jnp.asarray(v)))


@pytest.mark.parametrize("shape", [(64, 256), (48, 200)],
                         ids=["k64n", "ragged"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pattern", ["wide", "zeros", "signed_zeros",
                                     "flush"])
def test_block_align_matches_planes(pattern, dtype, shape):
    """Values and prescale bit-identical to the plane composition, for
    blocks along the last axis and along the first, alone and together."""
    for seed, shp in ((0, shape), (1, shape[::-1])):
        x = _data(pattern, shp, dtype, seed)
        ok = np.asarray(_single_matches_planes(x))
        assert ok.all(), (pattern, shp, ok)


def _pass2_matches_jnp(shape, tm, tn, axes, dtype, block):
    x = _data("wide", shape, dtype, seed=2)
    cfg = TFConfig(mode="separable", block=block)

    @jax.jit
    def check(x):
        with dispatch.override(use_pallas=False):
            want, s = tf.block_align(x, cfg, axes)
        got = block_align_pallas(x, s, axes=axes, block=block, fmt=cfg.fmt,
                                 tm=tm, tn=tn, interpret=True)
        return jnp.stack([jnp.all(_bits(a) == _bits(b))
                          for a, b in zip(got, want)])

    return np.asarray(check(x)).all()


@pytest.mark.parametrize("block", [32, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("axes", [(1,), (0,), (1, 0)])
def test_pallas_pass2_matches_jnp(axes, dtype, block):
    """The kernel, on a grid of several whole tiles over an operand padded
    to whole blocks, equals the jnp form bit for bit."""
    assert _pass2_matches_jnp((192, 320), 64, 128, axes, dtype, block)


@pytest.mark.parametrize("block", [32, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("axes", [(1,), (0,), (1, 0)])
def test_pallas_pass2_partial_tiles_match_jnp(axes, dtype, block):
    """The same where the last tile of each grid axis runs past the padded
    rows and columns (as the head's 151936 columns do, padded to 152064
    under 1024-wide tiles): its out-of-bounds part holds whole blocks of
    its own, whose values are never written."""
    assert _pass2_matches_jnp((320, 320), 256, 256, axes, dtype, block)


def test_dispatch_routes_to_pallas():
    """With Pallas asked for, block_align runs the kernel and still equals
    the jnp form."""
    x = _data("signed_zeros", (128, 256), jnp.float32, seed=3)

    def both(x, use_pallas):
        with dispatch.override(use_pallas=use_pallas, interpret=True):
            return tf.block_align(x, CFG, (1, 0))[0]

    assert "block_align_pallas" in str(jax.make_jaxpr(
        lambda x: both(x, True))(x))
    got, want = jax.jit(lambda x: (both(x, True), both(x, False)))(x)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(_bits(a)),
                                      np.asarray(_bits(b)))


@pytest.mark.parametrize("shape,types,kernel", [
    ((2, 2), "auto", False), ((2, 2), "manual", True), ((1,), "auto", True)],
    ids=["mesh_2x2", "shard_map_2x2", "mesh_1"])
def test_dispatch_under_a_mesh(shape, types, kernel):
    """A program XLA partitions over several devices takes the jnp form
    (the kernel has no partitioning rule); inside a shard_map over every
    axis, or on a one-device mesh, the kernel runs. Both equal the plane
    composition."""
    from jax.sharding import AbstractMesh, AxisType

    kind = {"auto": AxisType.Auto, "manual": AxisType.Manual}[types]
    mesh = AbstractMesh(shape, ("data", "model")[:len(shape)],
                        axis_types=(kind,) * len(shape))
    x = _data("wide", (64, 256), jnp.float32, seed=4)
    with jax.sharding.use_abstract_mesh(mesh), \
            dispatch.override(use_pallas=True, interpret=True):
        jaxpr = str(jax.make_jaxpr(
            lambda x: tf.block_align(x, CFG, (1, 0)))(x))
    assert ("block_align_pallas" in jaxpr) == kernel
    assert np.asarray(_single_matches_planes(x)).all()


def test_partitioned_jnp_form_bit_identical():
    """On a 2x2 mesh of CPU devices, with the kernel asked for, the aligned
    values and prescale of an operand sharded along either axis, both or
    neither equal those of the unsharded operand, bit for bit (ragged
    shapes included)."""
    from conftest import run_subprocess_devices

    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.core import timefloats as tf
from repro.kernels import dispatch
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = tf.TFConfig(mode="separable")
align = jax.jit(lambda x: tf.block_align(x, cfg, (1, 0)))
bad = 0
for shape, dt in (((256, 384), jnp.bfloat16), ((192, 200), jnp.float32)):
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * jnp.exp2(jnp.round(
        jax.random.normal(jax.random.PRNGKey(1), (shape[0], 1)) * 5))
    x = x.astype(dt)
    (w1, w0), ws = align(x)
    for spec in (P("data", None), P(None, "model"), P("data", "model"),
                 P(None, ("data", "model"))):
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        with jax.set_mesh(mesh), dispatch.override(use_pallas=True):
            (g1, g0), gs = align(xs)
        bits = lambda a: np.asarray(a).view(np.uint16)
        bad += not (np.array_equal(bits(g1), bits(w1))
                    and np.array_equal(bits(g0), bits(w0))
                    and float(gs) == float(ws))
print("MISMATCHES", bad)
"""
    assert "MISMATCHES 0" in run_subprocess_devices(code, n_devices=4)


def _planes_block_align(x, cfg, axes):
    """The values as the int8 planes give them (the kept composition)."""
    xs, s = tf._pow2_prescale(x, cfg)
    return tuple(
        tf.dequantize_input(tf.quantize_input(xs, cfg), x.shape[1]) if a == 1
        else tf.dequantize_weight(tf.quantize_weight(xs, cfg), x.shape[0])
        for a in axes), s


def test_train_step_values_equal_planes(monkeypatch):
    """A tiny separable model under full remat: loss and every gradient
    leaf from the two-pass values equal those from values built out of the
    int8 planes, bitwise."""
    cfg = family_cfg("attention", "separable", remat="full")
    batch = family_batch(cfg)
    lv, gv = loss_and_grads(cfg, batch)
    monkeypatch.setattr(tf, "block_align", _planes_block_align)
    lp, gp = loss_and_grads(cfg, batch)
    np.testing.assert_array_equal(lv, lp)
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(gv)[0],
                                 jax.tree_util.tree_flatten_with_path(gp)[0]):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def test_train_step_builds_no_planes():
    """Tracing the separable train step quantizes every operand two-pass:
    no plane-path quantization at all; each prepared input is one axis-1
    alignment, each cached weight one axis-0 alignment, and every backward
    aligns its cotangent in both layouts."""
    from repro.train import step as tsl

    cfg = family_cfg("attention", "separable", remat="full")
    tcfg = tsl.TrainConfig()
    state = jax.eval_shape(
        lambda: tsl.init_state(cfg, tcfg, jax.random.PRNGKey(0)))
    tf.reset_quant_trace_counts()
    jax.jit(tsl.make_train_step(cfg, tcfg)).lower(state, family_batch(cfg))
    c = tf.quant_trace_counts()
    assert sum(v for k, v in c.items() if k.startswith("planes.")) == 0, c
    assert c["prepare_weight"] > 0 and c["prepare_input"] > 0, c
    bwd = c["block_align.axis1"] - c["prepare_input"]
    assert bwd == c["block_align.axis0"] - c["prepare_weight"] > 0, c
