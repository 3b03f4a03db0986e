"""The quantized-operand cache contract (DESIGN.md §3) around the model:
the traced train step prepares no weight, serving (prefill and decode)
is unchanged by the cache, and a train step with the cache still learns.
The scanned stacks themselves are in test_cache_stack*.py, under remat in
test_cache_remat.py and op by op in test_cache.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import timefloats as tf
from repro.models import common

from _model_helpers import family_batch, family_cfg, mlp_model_cfg


def test_step_trace_contains_zero_weight_preparations():
    """The acceptance check for the scanned-stack cache: tracing the full
    fwd+bwd loss with the cache installed performs ZERO prepare_weight
    calls — every weight quantization lives in build_weight_cache, which
    train/step.py runs once per optimizer step outside the microbatch
    scan. (prepare_* counters tick once per Python invocation, i.e. per
    trace — a call inside the layer-scan body would execute per layer per
    microbatch; with the stacked cache there are none at all.)
    Tracing needs shapes only, so the parameters and the cache are
    abstract: nothing is initialised or quantized."""
    from repro.models import model as model_lib

    for family in ("attention", "moe"):
        cfg = family_cfg(family, "separable", cache=True)
        batch = family_batch(cfg)
        params = jax.eval_shape(
            lambda cfg=cfg: model_lib.init(cfg, jax.random.PRNGKey(0)))
        wcache = jax.eval_shape(
            lambda p, cfg=cfg: common.build_weight_cache(p, cfg), params)

        def loss(p, wcache, cfg=cfg, batch=batch):
            with common.weight_cache_scope(p, wcache):
                return model_lib.loss_fn(p, batch, cfg)[0]

        tf.reset_quant_trace_counts()
        jax.jit(jax.value_and_grad(loss)).lower(params, wcache)
        counts = tf.quant_trace_counts()
        assert counts["prepare_weight"] == 0, (family, counts)

        # control: without the weight cache the loss trace prepares
        # weights at every dense call site (executed per layer per
        # microbatch at run time)
        cfg_u = family_cfg(family, "separable", cache=False)

        def loss_u(p, cfg=cfg_u, batch=batch):
            return model_lib.loss_fn(p, batch, cfg)[0]

        tf.reset_quant_trace_counts()
        jax.jit(jax.value_and_grad(loss_u)).lower(params)
        assert tf.quant_trace_counts()["prepare_weight"] > 0


@pytest.mark.parametrize("family", ["hybrid", "attention", "ssm"])
def test_decode_prefill_unchanged_by_cache(family):
    """Serving is a training-path-free zone: prefill and decode_step
    logits are bit-identical whether TFConfig.cache is on or off (no
    weight_cache_scope is ever installed outside train/step.py)."""
    from repro.models import model as model_lib

    outs = {}
    for cache in (True, False):
        cfg = family_cfg(family, "separable", cache=cache)
        # jitted once each: the decode step is reused for every token
        prefill = jax.jit(
            lambda p, bt, c, cfg=cfg: model_lib.prefill(p, bt, cfg, c))
        decode_step = jax.jit(
            lambda p, c, tok, cfg=cfg: model_lib.decode_step(p, c, tok, cfg))
        params = model_lib.init(cfg, jax.random.PRNGKey(0))
        batch = family_batch(cfg, b=2, s=8)
        from repro.models.model import prefix_length
        max_len = 8 + prefix_length(cfg) + 4
        mc = model_lib.init_cache(cfg, 2, max_len)
        logits_p, mc = prefill(params, batch, mc)
        steps = [np.asarray(logits_p)]
        tok = jnp.argmax(logits_p[:, -1], axis=-1)[:, None]
        for _ in range(3):
            logits_d, mc = decode_step(params, mc, tok)
            steps.append(np.asarray(logits_d))
            tok = jnp.argmax(logits_d[:, -1], axis=-1)[:, None]
        outs[cache] = steps
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


def test_train_step_with_weight_cache_learns():
    """A jitted train step with the step-level weight cache installed (and
    grad accumulation, so the cache is hoisted outside the microbatch scan)
    still descends."""
    from repro.data.pipeline import DataPipeline
    from repro.optim.optimizers import OptimizerConfig
    from repro.train.step import TrainConfig, init_state, make_train_step

    cfg = dataclasses.replace(mlp_model_cfg(), n_layers=1, vocab_size=32)
    tcfg = TrainConfig(accum=2, optimizer=OptimizerConfig(
        name="adamw", lr=3e-3, schedule="constant"))
    state = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, tcfg))
    pipe = DataPipeline(cfg, batch=8, seq=16, seed=0, kind="markov",
                        prefetch=0)
    losses = []
    for i in range(10):
        state, m = step(state, pipe.batch_at(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < losses[0]
