"""Compile every Pallas kernel of the main path for a described TPU v5e.

Nothing runs: each test lowers a kernel at qwen3-0.6b widths (MLA at
deepseek-v3's) against shapes placed on one chip of a ``v5e:2x2``
topology that is described, not attached, and compiles it with the TPU
compiler. That compiler refuses what interpret mode accepts: blocks not
tiled (8, 128), lane slices off the tiling, more VMEM than a kernel may
use. Each test checks that the kernel survived as a Mosaic custom call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.timefloats import QuantizedOperand, TFConfig
from repro.kernels import ops
from repro.kernels.block_align import block_align_pallas
from repro.kernels.paged import gather_pages_pallas
from repro.kernels.paged_attn import paged_decode_attention, paged_decode_mla
from repro.kernels.sampling import sample_tokens

# qwen3-0.6b: d_model 1024, 16 query / 8 kv heads of 128, ffw 3072,
# vocab 151936. Serving: 8 slots over a 1024-page pool of 16-token pages.
M_TRAIN, D, FFW, VOCAB = 2048, 1024, 3072, 151936
SLOTS, PAGES, PAGE, TABLE = 8, 1024, 16, 16
H, HKV, HD = 16, 8, 128
CFG = TFConfig(mode="pallas")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep it out so nothing warns.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", before)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n", [(M_TRAIN, D, FFW), (SLOTS, D, VOCAB)])
def test_forward_matmul_compiles(spec, m, k, n):
    _assert_kernel(partial(ops.timefloats_matmul, cfg=CFG, interpret=False),
                   spec((m, k), jnp.float32), spec((k, n), jnp.float32))


@pytest.mark.parametrize("m,n,k", [(M_TRAIN, FFW, D), (M_TRAIN, VOCAB, D)])
def test_transposed_matmul_compiles(spec, m, n, k):
    qw = QuantizedOperand(spec((k // CFG.block, CFG.block, n), jnp.int8),
                          spec((k // CFG.block, n), jnp.float32))
    _assert_kernel(partial(ops.timefloats_matmul_transposed, k_dim=k,
                           cfg=CFG, interpret=False),
                   spec((m, n), jnp.float32), qw)


@pytest.mark.parametrize("shape,dt,axes", [
    ((M_TRAIN, D), jnp.bfloat16, (1,)),         # a layer input
    ((M_TRAIN, FFW), jnp.float32, (1, 0)),      # a cotangent, both reads
    ((M_TRAIN, VOCAB), jnp.float32, (1, 0)),    # the head's cotangent
    ((SLOTS, D), jnp.bfloat16, (1,)),           # a decode step's input
    ((28, D, FFW), jnp.float32, (0,)),          # a layer stack's weights
], ids=["input", "cotangent", "head", "decode", "weights"])
def test_block_align_compiles(spec, shape, dt, axes):
    fn = partial(block_align_pallas, axes=axes, block=CFG.block, fmt=CFG.fmt)
    if len(shape) == 3:
        fn = jax.vmap(fn)
    _assert_kernel(fn, spec(shape, dt), spec(shape[:-2], jnp.float32))


def test_sharded_linear_compiles_without_kernel(topo, spec):
    """The separable linear's forward and backward under a 2x2 mesh, with
    the Pallas kernels asked for: XLA partitions the program, so the
    aligner takes its jnp form (a Mosaic call cannot be partitioned) and
    the program compiles with no custom call. On one chip the kernel is
    there."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.core import timefloats as tf
    from repro.kernels import dispatch

    cfg = TFConfig(mode="separable")

    def grads(x, w):
        return jax.grad(lambda x, w: jnp.sum(
            tf.linear(x, w, cfg).astype(jnp.float32) ** 2), (0, 1))(x, w)

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    sharded = [jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(
        mesh, PartitionSpec(*axes))) for shape, dt, axes in (
            ((M_TRAIN, D), jnp.bfloat16, ("data", None)),
            ((D, FFW), jnp.float32, (None, "model")))]
    with dispatch.override(use_pallas=True, interpret=False):
        with jax.set_mesh(mesh):
            text = jax.jit(grads).lower(*sharded).compile().as_text()
        assert "tpu_custom_call" not in text
        _assert_kernel(grads, spec((M_TRAIN, D), jnp.bfloat16),
                       spec((D, FFW), jnp.float32))


def test_gqa_paged_decode_compiles(spec):
    pool = spec((PAGES, PAGE, HKV, HD), jnp.bfloat16)
    _assert_kernel(partial(paged_decode_attention, n_splits=2,
                           use_pallas=True, interpret=False),
                   spec((SLOTS, H, HD), jnp.bfloat16), pool, pool,
                   spec((SLOTS, TABLE), jnp.int32), spec((SLOTS,), jnp.int32))


def test_mla_paged_decode_compiles(spec):
    h, c, r = 128, 512, 64  # deepseek-v3 absorbed MLA
    _assert_kernel(partial(paged_decode_mla, scale=0.07, n_splits=2,
                           use_pallas=True, interpret=False),
                   spec((SLOTS, h, c), jnp.bfloat16),
                   spec((SLOTS, h, r), jnp.bfloat16),
                   spec((PAGES, PAGE, c), jnp.bfloat16),
                   spec((PAGES, PAGE, r), jnp.bfloat16),
                   spec((SLOTS, TABLE), jnp.int32), spec((SLOTS,), jnp.int32))


def test_page_gather_compiles(spec):
    _assert_kernel(partial(gather_pages_pallas, interpret=False),
                   spec((PAGES, PAGE, HKV, HD), jnp.bfloat16),
                   spec((SLOTS, TABLE), jnp.int32))


def test_sampling_compiles(spec):
    _assert_kernel(partial(sample_tokens, use_pallas=True, interpret=False),
                   spec((SLOTS, VOCAB), jnp.float32),
                   spec((SLOTS,), jnp.float32),
                   spec((2,), jnp.uint32),
                   spec((SLOTS,), jnp.int32), spec((SLOTS,), jnp.int32))
