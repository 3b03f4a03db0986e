"""Model-level helpers shared by the test modules: the smallest grouped-scan
config per layer family, batches, and loss+grads as train/step.py computes
them. Not a test module."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.timefloats import TFConfig
from repro.models import common

MODES = ["exact", "separable", "pallas"]


def mlp_model_cfg(mode="separable"):
    """Reduced qwen3-0.6b with TimeFloats projections."""
    from repro.configs import get_config, reduced_for_smoke

    cfg = reduced_for_smoke(get_config("qwen3-0.6b"))
    return dataclasses.replace(cfg, quant="timefloats", tf=TFConfig(mode=mode))


def family_cfg(family, mode, cache=True, remat="none"):
    """Smallest grouped-scan config exercising `family`'s block stack."""
    from repro.configs import get_config, reduced_for_smoke
    from repro.configs.base import MLAConfig

    arch = {"attention": "qwen3-0.6b", "mla": "deepseek-v3-671b",
            "ssm": "mamba2-1.3b", "hybrid": "hymba-1.5b",
            "moe": "deepseek-v3-671b"}[family]
    cfg = reduced_for_smoke(get_config(arch))
    tiny_mla = MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                         qk_nope_head_dim=16, qk_rope_head_dim=8,
                         v_head_dim=16)
    ch = dict(d_model=64, vocab_size=128, quant="timefloats", remat=remat,
              tf=TFConfig(mode=mode, cache=cache), q_block=32, kv_block=32)
    if cfg.n_heads:
        ch.update(n_heads=2, n_kv_heads=1, head_dim=32)
    if cfg.d_ff:
        ch["d_ff"] = 128
    if family == "mla":
        # pure MLA+MLP stack: drop the MoE FFN so the scatter-dispatch
        # noise (see the moe notes below) stays out of this family's run
        ch.update(family="dense", moe=None, n_layers=2, mla=tiny_mla)
    if family == "moe":
        ch["mla"] = tiny_mla
        ch["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=2, d_expert=32, shared_d_ff=32,
            dense_d_ff=64)
    if cfg.ssm:
        ch["ssm"] = dataclasses.replace(cfg.ssm, d_state=8, head_dim=16,
                                        chunk=16)
    if cfg.hybrid:
        ch["hybrid"] = dataclasses.replace(cfg.hybrid, meta_tokens=4,
                                           sliding_window=16)
    return dataclasses.replace(cfg, **ch)


def family_batch(cfg, b=2, s=8, seed=1):
    k1, k2, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab_size),
            "labels": jax.random.randint(k2, (b, s), 0, cfg.vocab_size),
            "mask": jnp.ones((b, s), jnp.float32)}


def make_batch(cfg, b, s, key):
    """A random batch for any architecture (audio codebooks, VLM patches)."""
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (b, s) if cfg.family != "audio" else (b, s, cfg.num_codebooks)
    batch = {
        "tokens": jax.random.randint(k1, shape, 0, cfg.vocab_size),
        "labels": jax.random.randint(k2, shape, 0, cfg.vocab_size),
        "mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            k3, (b, cfg.num_prefix_tokens, cfg.d_model), jnp.float32)
    return batch


def loss_and_grads(cfg, batch, jit=True):
    """loss+grads exactly as train/step.py computes them: weight cache
    built outside the grad trace, scope installed around the loss."""
    from repro.models import model as model_lib

    params = model_lib.init(cfg, jax.random.PRNGKey(0))

    def loss(p):
        wc = common.build_weight_cache(p, cfg)
        with common.weight_cache_scope(p, wc):
            return model_lib.loss_fn(p, batch, cfg)[0]

    fn = jax.value_and_grad(loss)
    if jit:
        fn = jax.jit(fn)
    l, g = fn(params)
    return np.asarray(l), jax.tree.map(np.asarray, g)


def assert_grads_identical(family, gc, gu):
    """Bitwise by default. The MoE dispatch region is compared to f32
    reassociation tolerance ONLY: XLA compiles the token-contraction dW
    dots adjacent to the scatter/gather dispatch with program-dependent
    reduction order (the dW sum mixes per-token pow2 scales, so order
    changes last bits; observed on wd/shared_wd, pre-existing at the
    residual-cache level). test_stacked_cache_moe_bit_identical_op_by_op
    proves the arithmetic itself is bit-identical."""
    fc = jax.tree_util.tree_flatten_with_path(gc)[0]
    fu = jax.tree_util.tree_flatten_with_path(gu)[0]
    for (path, a), (_, b) in zip(fc, fu):
        name = jax.tree_util.keystr(path)
        if family == "moe" and "['ffn']" in name:
            np.testing.assert_allclose(a, b, rtol=1e-2, atol=2e-3,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def assert_stacked_cache_bit_identity(family, mode, remat="none"):
    """Loss AND grads with the stacked scan cache on == TFConfig.cache=False
    (the scanned-stack contract of DESIGN.md §3)."""
    cfg_c = family_cfg(family, mode, cache=True, remat=remat)
    cfg_u = family_cfg(family, mode, cache=False, remat=remat)
    batch = family_batch(cfg_c)
    lc, gc = loss_and_grads(cfg_c, batch)
    lu, gu = loss_and_grads(cfg_u, batch)
    np.testing.assert_array_equal(lc, lu)
    assert_grads_identical(family, gc, gu)
