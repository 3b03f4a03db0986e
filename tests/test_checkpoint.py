"""Checkpoint manager: atomicity, keep-N, async, restore, elastic reshard."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager

from conftest import run_subprocess_devices


def tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)),
                   "b": jnp.arange(16, dtype=jnp.bfloat16)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    t = tree()
    mgr.save(7, t)
    assert mgr.latest_step() == 7
    target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    out = mgr.restore(7, target)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_async_save_and_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(1, tree())
    mgr.wait()
    assert mgr.latest_step() == 1


def test_keep_n_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree())
    assert mgr.all_steps() == [3, 4]
    files = os.listdir(tmp_path)
    assert not any("step_1" in f or "step_2" in f for f in files)


def test_no_done_marker_is_invisible(tmp_path):
    """A write that died before the .done marker must not be listed."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(5, tree())
    os.remove(os.path.join(tmp_path, "step_5.done"))
    assert mgr.latest_step() is None


def test_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, tree())
    bad = {"params": {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
                      "b": jax.ShapeDtypeStruct((16,), jnp.bfloat16)},
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    with pytest.raises(ValueError):
        mgr.restore(1, bad)


def test_elastic_restore_different_mesh(tmp_path):
    """Save on 1 device, restore onto a 8-device mesh with shardings —
    the elastic-scaling path (checkpoints are logical arrays)."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    mgr.save(3, t)

    code = f"""
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
mgr = CheckpointManager({str(tmp_path)!r})
target = {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
sh = {{"w": NamedSharding(mesh, P("data", "model"))}}
out = mgr.restore(3, target, shardings=sh)
assert out["w"].sharding.spec == P("data", "model"), out["w"].sharding
np.testing.assert_array_equal(
    np.asarray(out["w"]), np.arange(64, dtype=np.float32).reshape(8, 8))
print("ELASTIC_OK", len(out["w"].addressable_shards))
"""
    out = run_subprocess_devices(code, n_devices=8)
    assert "ELASTIC_OK 8" in out


def test_trainer_auto_resume(tmp_path):
    """run_loop resumes from the latest checkpoint and replays the stream."""
    import dataclasses

    from repro.configs import get_config, reduced_for_smoke
    from repro.data.pipeline import DataPipeline
    from repro.train.step import TrainConfig, init_state, make_train_step
    from repro.train.trainer import LoopConfig, run_loop

    cfg = reduced_for_smoke(get_config("qwen3-0.6b"))
    cfg = dataclasses.replace(cfg, quant="none", n_layers=1)
    tcfg = TrainConfig(accum=1)
    step_fn = jax.jit(make_train_step(cfg, tcfg))
    pipe = DataPipeline(cfg, batch=2, seq=16, kind="lm", prefetch=0)
    loop = LoopConfig(total_steps=6, ckpt_every=3, ckpt_dir=str(tmp_path),
                      log_every=100)

    s0 = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    s_a, rep_a = run_loop(s0, step_fn, pipe.batch_at, loop)
    assert rep_a.resumed_from is None and rep_a.final_step == 6

    # "crash" and restart from scratch: must resume from step 6 checkpoint
    s1 = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    loop2 = dataclasses.replace(loop, total_steps=8)
    s_b, rep_b = run_loop(s1, step_fn, pipe.batch_at, loop2)
    assert rep_b.resumed_from == 6
    assert rep_b.steps_run == 2
    assert rep_b.final_step == 8
