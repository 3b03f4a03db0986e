"""Distribution machinery: sharding rules, ZeRO, gradient compression,
pipeline parallelism. Multi-device cases run in subprocesses with fake CPU
devices so the main test process keeps the 1-device contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_subprocess_devices
from repro.parallel import compression
from repro.parallel.pipeline import bubble_fraction, split_stages
from repro.parallel.sharding import resolve_spec, DEFAULT_RULES


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_resolve_spec_basic():
    mesh = FakeMesh({"data": 16, "model": 16})
    # embed -> data, vocab -> model
    s = resolve_spec((151936, 1024), ("vocab", "embed"), DEFAULT_RULES, mesh)
    assert s == P("model", "data")


def test_resolve_spec_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    # kv_heads=8 does not divide model=16 -> replicated
    s = resolve_spec((1024, 8, 128), ("embed", "kv_heads", "head_dim"),
                     DEFAULT_RULES, mesh)
    assert s == P("data", None, None)


def test_resolve_spec_conflict_first_come():
    mesh = FakeMesh({"data": 16, "model": 16})
    # experts takes model; ffw then falls back to replication
    s = resolve_spec((256, 7168, 2048), ("experts", "embed", "ffw"),
                     DEFAULT_RULES, mesh)
    assert s == P("model", "data", None)


def test_resolve_spec_multi_axis_batch():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    s = resolve_spec((256, 4096), ("batch", None), DEFAULT_RULES, mesh)
    assert s == P(("pod", "data"), None)


def test_make_mesh_axis_types_auto():
    """Launcher meshes are Auto-typed (JAX 0.9's make_mesh defaults to
    Explicit, under which the FSDP-sharded dot raises) and take a device
    subset when asked."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_zero_shard_spec():
    code = """
import jax
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.zero import zero_shard_spec
mesh = make_mesh((4, 2), ("data", "model"))
# fully replicated 2D state -> first divisible dim gets "data"
# (specs are rank-padded, so compare against the padded form)
s = zero_shard_spec(P(), (8, 6), mesh, axes=("data",))
assert s == P("data", None), s
# dim0 taken -> dim1
s = zero_shard_spec(P("data"), (8, 8), mesh, axes=("model",))
assert s == P("data", "model"), s
# nothing divisible -> unchanged
s = zero_shard_spec(P(), (3, 5), mesh, axes=("data",))
assert s == P(None, None), s
print("ZERO_OK")
"""
    assert "ZERO_OK" in run_subprocess_devices(code, n_devices=8)


def test_compression_error_feedback_unbiased():
    """Across steps, compressed psum average == true average (error feedback
    re-injects residuals)."""
    code = """
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel import compression

mesh = make_mesh((4,), ("pod",))
grads_seq = [
    {"w": jax.random.normal(jax.random.PRNGKey(s), (4, 33))}
    for s in range(20)
]

@jax.jit
def one_step(g, state):
    f = jax.shard_map(
        lambda g_, e_: compression.compressed_psum_tree(
            g_, compression.CompressionState(error=e_), "pod"),
        mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod"), P()),
        check_vma=False)
    out, new_state, wire = f(g, state.error)
    return out, new_state, wire

state = compression.init_state({"w": jnp.zeros((4, 33))})
tot_comp = np.zeros((33,))
tot_true = np.zeros((33,))
for g in grads_seq:
    out, state, wire = one_step(g, state)
    tot_comp += np.asarray(out["w"]).mean(0)
    tot_true += np.asarray(g["w"]).mean(0)
err = np.abs(tot_comp - tot_true).max() / (np.abs(tot_true).max() + 1e-9)
assert err < 0.05, err
assert float(wire) == 33 + 4  # int8 payload + scale, per shard
print("COMP_OK", err)
"""
    assert "COMP_OK" in run_subprocess_devices(code, n_devices=4)


def test_compression_wire_bytes_ratio():
    # static accounting: f32 = 4 bytes/elem vs int8 + one 4-byte scale
    int8_bytes = 1024 + 4
    f32_bytes = 1024 * 4
    assert f32_bytes / int8_bytes > 3.9


def test_pipeline_forward_matches_sequential():
    code = """
import jax, numpy as np
import jax.numpy as jnp
from repro.parallel.pipeline import (pipeline_forward, split_stages,
                                     make_layer_stage_fn)
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("stage",))
L, D, M, B = 8, 16, 6, 4
key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (L, D, D)) / np.sqrt(D)}

def layer_fn(lp, x):
    return jnp.tanh(x @ lp["w"])

stage_fn = make_layer_stage_fn(layer_fn)
x = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))

stage_params = split_stages(params, 4)
y = pipeline_forward(stage_fn, stage_params, x, mesh=mesh, axis="stage")

# sequential reference
def seq(x):
    h = x
    for l in range(L):
        h = layer_fn({"w": params["w"][l]}, h)
    return h
want = jax.vmap(seq)(x)
np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-4, atol=2e-4)

# autodiff through the pipeline
def loss(sp):
    return jnp.sum(pipeline_forward(stage_fn, sp, x, mesh=mesh, axis="stage") ** 2)
g = jax.grad(loss)(stage_params)
assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))
print("PIPE_OK")
"""
    assert "PIPE_OK" in run_subprocess_devices(code, n_devices=4)


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert bubble_fraction(1, 8) == 0.0


def test_split_stages_shapes():
    p = {"w": jnp.zeros((8, 3, 3))}
    s = split_stages(p, 4)
    assert s["w"].shape == (4, 2, 3, 3)
    with pytest.raises(AssertionError):
        split_stages({"w": jnp.zeros((7, 3))}, 4)


def test_train_step_sharded_end_to_end():
    """Full sharded train step on a 4x2 mesh (mini production mesh):
    loss finite, params updated, batch actually sharded."""
    code = """
import dataclasses
import jax, numpy as np
import jax.numpy as jnp
from repro.configs import get_config, reduced_for_smoke
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.train import step as tsl
from repro.data.synthetic import lm_batch
from repro.launch.mesh import make_mesh

cfg = reduced_for_smoke(get_config("qwen3-0.6b"))
mesh = make_mesh((4, 2), ("data", "model"))
rules = shd.make_rules(mesh)
tcfg = tsl.TrainConfig(accum=2)
state = tsl.init_state(cfg, tcfg, jax.random.PRNGKey(0))
s_axes = tsl.state_axes(cfg, tcfg)
s_shard = shd.tree_shardings(s_axes, jax.tree.map(lambda a: a, state), mesh, rules)
state = jax.device_put(state, s_shard)
batch = lm_batch(cfg, 8, 32, jax.random.PRNGKey(1))
b_shard = shd.batch_shardings(batch, mesh, rules)
batch = jax.device_put(batch, b_shard)
step_fn = tsl.make_train_step(cfg, tcfg)
def fn(s, b):
    with shd.sharding_context(mesh, rules):
        return step_fn(s, b)
jitted = jax.jit(fn, in_shardings=(s_shard, b_shard), donate_argnums=(0,))
with jax.set_mesh(mesh):
    new_state, metrics = jitted(state, batch)
loss = float(metrics["loss"])
assert np.isfinite(loss), loss
assert int(new_state.step) == 1
print("SHARDED_STEP_OK", loss)
"""
    assert "SHARDED_STEP_OK" in run_subprocess_devices(code, n_devices=8)


def test_sharded_matches_single_device():
    """Same seed, same batch: the 8-device sharded step must produce the
    same loss as single-device execution (SPMD correctness)."""
    code = """
import dataclasses
import jax, numpy as np
import jax.numpy as jnp
from repro.configs import get_config, reduced_for_smoke
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.train import step as tsl
from repro.data.synthetic import lm_batch
from repro.launch.mesh import make_mesh

cfg = reduced_for_smoke(get_config("phi3-mini-3.8b"))
cfg = dataclasses.replace(cfg, quant="none")
tcfg = tsl.TrainConfig(accum=1)
state = tsl.init_state(cfg, tcfg, jax.random.PRNGKey(0))
batch = lm_batch(cfg, 8, 32, jax.random.PRNGKey(1))
step_fn = tsl.make_train_step(cfg, tcfg)
_, m_single = jax.jit(step_fn)(state, batch)
l_single = float(m_single["loss"])

mesh = make_mesh((4, 2), ("data", "model"))
rules = shd.make_rules(mesh)
state2 = tsl.init_state(cfg, tcfg, jax.random.PRNGKey(0))
s_axes = tsl.state_axes(cfg, tcfg)
s_shard = shd.tree_shardings(s_axes, jax.tree.map(lambda a: a, state2), mesh, rules)
state2 = jax.device_put(state2, s_shard)
b_shard = shd.batch_shardings(batch, mesh, rules)
batch2 = jax.device_put(batch, b_shard)
def fn(s, b):
    with shd.sharding_context(mesh, rules):
        return step_fn(s, b)
with jax.set_mesh(mesh):
    _, m_shard = jax.jit(fn, in_shardings=(s_shard, b_shard))(state2, batch2)
l_shard = float(m_shard["loss"])
assert abs(l_single - l_shard) < 5e-3, (l_single, l_shard)
print("SPMD_MATCH_OK", l_single, l_shard)
"""
    assert "SPMD_MATCH_OK" in run_subprocess_devices(code, n_devices=8)
