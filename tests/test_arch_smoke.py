"""Per-architecture smoke tests (reduced configs): every assigned arch gets
init -> forward -> loss -> one train step on CPU, asserting output shapes
and finiteness (the harness smoke contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced_for_smoke
from repro.models import model as M
from repro.train.step import TrainConfig, init_state, make_train_step

from _model_helpers import make_batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_step(arch):
    cfg = reduced_for_smoke(get_config(arch))
    params = M.init(cfg, jax.random.PRNGKey(0))
    b, s = 2, 64
    batch = make_batch(cfg, b, s, jax.random.PRNGKey(1))

    logits, aux = jax.jit(
        lambda p, bt: M.forward(p, bt, cfg, train=False))(params, batch)
    text = s  # tokens fed == text length; prefix added inside
    if cfg.family == "audio":
        assert logits.shape == (b, text, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (b, text, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    tcfg = TrainConfig(accum=2)
    state = init_state(cfg, tcfg, jax.random.PRNGKey(2))
    step = make_train_step(cfg, tcfg)
    new_state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, l: a + float(jnp.sum(jnp.abs(l[0].astype(jnp.float32)
                                               - l[1].astype(jnp.float32)))),
        jax.tree.map(lambda a, b_: (a, b_), new_state.params, state.params),
        0.0)
    assert delta > 0.0
