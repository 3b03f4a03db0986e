"""TimeFloats matmul micro-benchmarks.

On this CPU container the Pallas kernel runs in interpret mode (Python), so
its wall time is NOT the TPU figure — we benchmark (a) the XLA separable
path wall-time vs a plain bf16 matmul (the quantization overhead XLA would
also pay on TPU hosts), (b) accuracy vs K for all modes, and (c) the
kernel's structural VMEM footprint per BlockSpec tile (the quantity that
determines TPU occupancy; see kernels/timefloats_matmul.py header).
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import timefloats as tf
from repro.core.timefloats import TFConfig


def timeit(fn, *args, iters=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _med_time(fn, *args, iters=3, reps=5):
    """Median-of-reps wall time in us (this 2-core container is noisy)."""
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) / iters * 1e6)
    return float(np.median(ts))


def _plane_scaled_matmul(x, w, cfg):
    """``matmul`` of the pow2-prescaled operands (int8 planes in separable
    mode), scales divided out."""
    xs, sx = tf._pow2_prescale(x, cfg)
    ws, sw = tf._pow2_prescale(w, cfg)
    return tf.matmul(xs, ws, cfg) / (sx * sw)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _legacy_linear(x, w, cfg):
    """The pre-cache training linear (the speedup baseline): raw float
    residuals; the backward re-quantizes w.T and x.T from float32 — three
    full re-decompositions + two materialized transposes per fwd+bwd, none
    of which XLA can CSE against the forward (different chunking axes)."""
    lead = x.shape[:-1]
    y = _plane_scaled_matmul(x.reshape(-1, x.shape[-1]), w, cfg)
    return y.reshape(*lead, w.shape[-1])


def _legacy_fwd(x, w, cfg):
    return _legacy_linear(x, w, cfg), (x, w)


def _legacy_bwd(cfg, res, g):
    x, w = res
    g2 = g.reshape(-1, g.shape[-1])
    x2 = x.reshape(-1, x.shape[-1])
    dx = _plane_scaled_matmul(g2, w.T, cfg).reshape(x.shape).astype(x.dtype)
    dw = _plane_scaled_matmul(x2.T, g2, cfg).astype(w.dtype)
    return dx, dw


_legacy_linear.defvjp(_legacy_fwd, _legacy_bwd)


def _fwdbwd_step_bench(report):
    """Quantized-operand cache win (DESIGN.md §3): a full fwd+bwd+update
    training step of a 2-layer MLP, separable mode, three implementations:

    legacy   — the pre-cache custom_vjp (re-quantize w.T/x.T in bwd).
    uncached — cfg.cache=False: the transposed-read backward, but from raw
               float residuals (re-quantization left to XLA CSE).
    cached   — quantized residuals + each weight's cache entry prepared
               once per step before the loss (the models/common.py +
               train/step.py hook).

    cached and uncached are bit-identical by contract (asserted); legacy
    shares the forward bits but its backward pre-dates the transposed-read
    semantics, so it is the cost baseline only.

    The step is accum=1 (one jitted fwd+bwd+update program, the common
    case). With a grad-accumulation scan, XLA's loop-invariant code motion
    already hoists the loop-invariant weight quantization for every
    variant, compressing the measured gap — the weight cache makes that
    amortization explicit and portable instead of optimizer-dependent."""
    d, rows = 1024, 16
    kw1, kw2, kx, ky = jax.random.split(jax.random.PRNGKey(42), 4)
    ws = {"w1": jax.random.normal(kw1, (d, d)) / np.sqrt(d),
          "w2": jax.random.normal(kw2, (d, d)) / np.sqrt(d)}
    xb = jax.random.normal(kx, (rows, d))
    yb = jax.random.normal(ky, (rows, d))

    def make_step(kind: str):
        cfg = TFConfig(mode="separable", cache=(kind == "cached"))

        def step(ws, x, tgt):
            if kind == "cached":
                pws = {k: tf.prepare_weight(ws[k], cfg)  # once per step
                       for k in ws}

            def loss(ws_):
                if kind == "cached":
                    h = jax.nn.relu(
                        tf.linear_cached(x, ws_["w1"], pws["w1"], cfg))
                    y = tf.linear_cached(h, ws_["w2"], pws["w2"], cfg)
                else:
                    lin = _legacy_linear if kind == "legacy" else tf.linear
                    h = jax.nn.relu(lin(x, ws_["w1"], cfg))
                    y = lin(h, ws_["w2"], cfg)
                return jnp.mean((y - tgt) ** 2)

            g = jax.grad(loss)(ws)
            return jax.tree.map(lambda w, gg: w - 1e-3 * gg, ws, g)

        return jax.jit(step)

    steps = {k: make_step(k) for k in ("legacy", "uncached", "cached")}
    outs = {k: jax.tree.map(np.asarray, s(ws, xb, yb))
            for k, s in steps.items()}
    identical = all(np.array_equal(outs["uncached"][k], outs["cached"][k])
                    for k in ws)
    times = {k: _med_time(s, ws, xb, yb, iters=5, reps=7)
             for k, s in steps.items()}

    report("kernel/step_legacy_us", times["legacy"],
           f"2x({d}x{d}) MLP, {rows} rows, pre-cache bwd")
    report("kernel/step_uncached_us", times["uncached"],
           "transposed-read bwd, float residuals")
    report("kernel/step_cached_us", times["cached"],
           "quantized residuals + per-step weight cache")
    report("kernel/step_cache_speedup_x",
           times["legacy"] / times["cached"],
           "vs pre-cache bwd; target >= 1.5x (ISSUE 1 acceptance)")
    report("kernel/step_cache_bit_identical", int(identical),
           "cached vs uncached updated weights, bitwise")
    assert identical, "cache changed the arithmetic (must be bit-identical)"


def _scanned_step_bench(report):
    """Scanned-stack weight cache win (DESIGN.md §3, ISSUE 2): a jitted
    fwd+bwd+update train step of a grouped-scan LM — a reduced
    qwen3-0.6b-shaped model whose layer stack runs under lax.scan, with
    grad-accumulation microbatching — cached (stacked PreparedOperands
    threaded through the layer scan, built once per step) vs
    TFConfig.cache=False (every scan iteration re-quantizes its layer's
    weights, once per microbatch). This measures the per-microbatch →
    per-step conversion on a real scanned model rather than asserting it.

    The trace-time prepare_weight counters are reported alongside: cached
    traces contain exactly one preparation per dense-eligible weight (all
    in build_weight_cache, outside the scans); uncached traces prepare at
    every dense call site *inside* the scan bodies, so that work executes
    layers x microbatches times per step."""
    import dataclasses

    from repro.configs import get_config, reduced_for_smoke
    from repro.data.pipeline import DataPipeline
    from repro.train.step import TrainConfig, init_state, make_train_step

    # Weight-dominated regime (what the cache targets): production models
    # run d_model >= 1024 with modest per-microbatch token counts, so the
    # per-layer weight (re)quantization is a material slice of the step.
    # A token-dominated shrink (d=128, 256 tokens) buries the effect under
    # activation quantization and shows ~1.0x.
    base = dataclasses.replace(reduced_for_smoke(get_config("qwen3-0.6b")),
                               n_layers=4, d_model=512, n_heads=4,
                               n_kv_heads=2, head_dim=128, d_ff=1024)
    tcfg = TrainConfig(accum=2)
    batch = DataPipeline(base, batch=4, seq=16, seed=0, kind="markov",
                         prefetch=0).batch_at(0)

    times, counts, losses = {}, {}, {}
    for kind in ("cached", "uncached"):
        cfg = dataclasses.replace(
            base, quant="timefloats",
            tf=TFConfig(mode="separable", cache=(kind == "cached")))
        state = init_state(cfg, tcfg, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, tcfg))
        tf.reset_quant_trace_counts()
        _, metrics = step(state, batch)  # compile + warm
        counts[kind] = tf.quant_trace_counts()["prepare_weight"]
        losses[kind] = float(metrics["loss"])
        times[kind] = _med_time(step, state, batch, iters=3, reps=5)

    report("kernel/scan_step_cached_us", times["cached"],
           "4-layer scanned qwen3 shape, accum=2, stacked weight cache")
    report("kernel/scan_step_uncached_us", times["uncached"],
           "same model, TFConfig.cache=False (per-microbatch re-quant)")
    report("kernel/scan_step_cache_speedup_x",
           times["uncached"] / times["cached"],
           "per-step vs per-microbatch weight quantization")
    report("kernel/scan_step_prepares_cached", counts["cached"],
           "prepare_weight per step trace == dense-eligible weights")
    report("kernel/scan_step_prepares_uncached", counts["uncached"],
           "trace-time count; executes x layers x microbatches at run time")
    identical = losses["cached"] == losses["uncached"]
    report("kernel/scan_step_loss_bit_identical", int(identical),
           "first-step loss, cached vs uncached")
    assert identical, (losses, "scan cache changed the loss bits")


def run(report):
    _fwdbwd_step_bench(report)
    _scanned_step_bench(report)
    m, k, n = 256, 1024, 512
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)

    bf16 = jax.jit(lambda a, b: (a.astype(jnp.bfloat16)
                                 @ b.astype(jnp.bfloat16)))
    sep = jax.jit(lambda a, b: tf.matmul_separable(a, b, TFConfig()))
    t_bf = timeit(bf16, x, w)
    t_sep = timeit(sep, x, w)
    report("kernel/bf16_matmul_us", t_bf, f"{m}x{k}x{n} XLA CPU")
    report("kernel/timefloats_separable_us", t_sep,
           f"quantize+align+int-mac, overhead {t_sep / t_bf:.1f}x")

    # accuracy vs K (error grows ~sqrt(K) for FP8 operands)
    for kk in (64, 256, 1024):
        xx = jax.random.normal(jax.random.PRNGKey(kk), (64, kk))
        ww = jax.random.normal(jax.random.PRNGKey(kk + 1), (kk, 64))
        ref = xx @ ww
        for mode in ("exact", "separable"):
            y = tf._scaled_matmul(xx, ww, TFConfig(mode=mode))
            rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
            report(f"kernel/relerr_{mode}_k{kk}", rel * 100, "% rel L2")

    # structural VMEM accounting for the default BlockSpec tile
    bm, bn, bc, blk = 256, 256, 8, 64
    vmem = (bc * bm * blk  # qx int8
            + bc * blk * bn  # qw int8
            + bm * bn * 4    # out f32
            + bc * (bm + bn) * 4)  # scales
    report("kernel/vmem_per_tile_KiB", vmem / 1024,
           "default tile; v5e VMEM = 16 MiB")
    assert vmem < 16 * 1024 * 1024 / 4  # 4x headroom for double buffering

    # sparsity the alignment produces on wide-dynamic-range data
    xw = jax.random.normal(jax.random.PRNGKey(7), (32, 256)) * jnp.exp2(
        jax.random.randint(jax.random.PRNGKey(8), (32, 256), -6, 7
                           ).astype(jnp.float32))
    ws = jax.random.normal(jax.random.PRNGKey(9), (256, 32))
    report("kernel/shift_sparsity_widerange",
           float(tf.expected_sparsity(xw, ws, TFConfig())) * 100,
           "% chunk terms zeroed (paper: 'enhances sparsity')")
