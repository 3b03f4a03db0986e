"""Bring-up smoke: the main path on a TPU, at qwen3-0.6b's published widths.

    python3 chip_smoke.py               # one chip: kernels, train, serve
    python3 chip_smoke.py --four-chips  # sharded train on a 2x2 mesh only

Everything runs in this one process, through the entry points a user
calls (``repro.launch.train.main`` and ``repro.launch.serve.main``), with
random weights and synthetic data made from ``--seed``; nothing is read
from disk. A phase that fails makes the script exit non-zero. So does a
machine without a TPU, before any phase runs. The last line of standard
output is a JSON object naming the device, printed only when every phase
passed.

Phases (one chip):

- device:  the platform must be ``tpu``; prints kind, count and versions.
- kernels: every Pallas kernel of the path, compiled, against its jnp
  reference (forward and transposed TimeFloats matmul, paged GQA decode,
  page gather, sampling), each within the tolerance stated beside it.
- train:   ``launch/train.py`` for a few steps (finite loss), then one step
  from one init and batch in ``mode="pallas"`` and ``mode="separable"``,
  whose losses must agree.
- serve:   ``launch/serve.py --paged --prefix-len 32``, greedy, every
  request served with the pool conserved; the same drain with the jnp
  reference kernels must give mostly the same tokens.

``--four-chips`` runs the full-width train step through ``launch/train.py``
on a (data=2, model=2) mesh and on one chip of the same host, and compares
their per-step losses and how the state is spread over the devices.

The train, serve and four-chip phases take ``reduced=True`` for a
rehearsal off the chip at the configuration's reduced size; the script
itself never passes it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import io
import json
import math
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen3-0.6b"
OUT = ROOT / "chiprun_out" / "smoke"

# The repo's bf16 greedy-parity gate (DESIGN §10): bf16 logits tie or flip
# at near-equal maxima, after which a greedy stream legitimately diverges.
TOKEN_IDENTITY_MIN = 0.75
# pallas vs separable train loss: both modes multiply the same quantized
# operands exactly (int8 MACs vs exact bf16 products) and accumulate in
# f32, only in another order. The E4M4 quantizer at every matmul input
# turns such a last-bit difference into a 2^-5 flip wherever an element
# sits on a rounding boundary, and 28 layers compound it: one v5e step
# measured 2.5e-4.
TRAIN_MODE_RTOL = 1e-3
# Sharded vs one-chip losses, per step: the mesh splits f32 reductions
# (FSDP gathers, TP partial sums) into another order; AdamW's early
# sign-like updates may amplify that over the steps.
SHARDED_RTOL = 1e-2


class _Tee(io.TextIOBase):
    """Write-through to stdout that also keeps what was written."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def _run_capturing(fn, argv):
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = fn(argv)
    gc.collect()  # free the launcher's device state before the next phase
    return rc, tee.buf.getvalue()


def _losses(text):
    return [float(x) for x in re.findall(r"^step\s+\d+ loss (\S+)", text,
                                         re.M)]


def _check(ok, what):
    if not ok:
        raise AssertionError(what)


def phase_device(jax, want: int):
    devs = jax.devices()
    d = devs[0]
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    _check(d.platform == "tpu", f"no TPU: JAX found {d.platform}")
    _check(len(devs) >= want, f"need {want} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_kernels(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.timefloats import TFConfig, quantize_weight
    from repro.kernels import ops, ref
    from repro.kernels.paged import gather_pages, gather_pages_ref
    from repro.kernels.paged_attn import paged_decode_attention
    from repro.kernels.sampling import sample_tokens

    cfg = TFConfig(mode="pallas")
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    m, d, ffw, vocab = 8 * 256, 1024, 3072, 151936

    def rel_err(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    # Forward: int8 MACs are exact (int32) in both; the per-chunk f32
    # scale-and-accumulate may be fused in another order -> a few ulp.
    x = jax.random.normal(next(ks), (m, d), jnp.float32)
    w = jax.random.normal(next(ks), (d, ffw), jnp.float32) * 0.05
    err = rel_err(ops.timefloats_matmul(x, w, cfg, interpret=False),
                  ref.timefloats_matmul_ref(x, w, cfg))
    print(f"kernel forward matmul ({m}x{d}x{ffw}): max err {err:.3g} "
          f"of max|ref| (tol 1e-5)")
    _check(err <= 1e-5, "forward matmul off its reference")

    # Transposed: exact bf16 products on both, f32 sums over N=3072 in
    # another order (tiles of 512 vs XLA's) -> far below 1e-5 of the max.
    g = jax.random.normal(next(ks), (m, ffw), jnp.float32)
    qw = quantize_weight(w, cfg)
    err = rel_err(
        ops.timefloats_matmul_transposed(g, qw, k_dim=d, cfg=cfg,
                                         interpret=False),
        ref.timefloats_matmul_transposed_ref(g, qw, d, cfg))
    print(f"kernel transposed matmul ({m}x{ffw}x{d}): max err {err:.3g} "
          f"of max|ref| (tol 1e-5)")
    _check(err <= 1e-5, "transposed matmul off its reference")

    # Paged GQA decode over a 1024-page pool (16 tokens of 8x128 bf16 per
    # page). The reference runs at f32 precision; the kernel's MXU may
    # round the f32 softmax weights to bf16 (2^-9 relative) before p.V,
    # so outputs (convex combinations of N(0,1) values) may move ~1e-2.
    slots, pages, page, table = 8, 1024, 16, 16
    q = jax.random.normal(next(ks), (slots, 16, 128), jnp.bfloat16)
    kp = jax.random.normal(next(ks), (pages, page, 8, 128), jnp.bfloat16)
    vp = jax.random.normal(next(ks), (pages, page, 8, 128), jnp.bfloat16)
    pt = jax.random.permutation(next(ks), pages - 1)[:slots * table] + 1
    pt = pt.reshape(slots, table).astype(jnp.int32)
    lens = jax.random.randint(next(ks), (slots,), 1, table * page + 1)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_attention(q, kp, vp, pt, lens, n_splits=2,
                                      use_pallas=False)
    got = paged_decode_attention(q, kp, vp, pt, lens, n_splits=2,
                                 use_pallas=True, interpret=False)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"kernel paged GQA decode ({slots} slots, {pages} pages): "
          f"max abs err {err:.3g} (tol 1e-2)")
    _check(err <= 1e-2, "paged GQA decode off its reference")

    # Page gather is a copy: exact.
    same = bool(jnp.array_equal(gather_pages(kp, pt, use_pallas=True),
                                gather_pages_ref(kp, pt)))
    print(f"kernel page gather: identical={same}")
    _check(same, "page gather differs from pool[page_table]")

    # Sampling: an argmax with a fixed tie rule over the same row bits on
    # both paths: exact, greedy and tempered rows alike.
    lg = jax.random.normal(next(ks), (slots, vocab), jnp.float32) * 4
    temps = jnp.asarray([0.0, 0.7, 1.0, 0.0, 1.3, 0.2, 0.0, 0.9])
    tags = jnp.arange(slots, dtype=jnp.int32)
    counters = jnp.arange(slots, dtype=jnp.int32) * 3
    key = jax.random.PRNGKey(seed + 1)
    got = sample_tokens(lg, temps, key, tags, counters, use_pallas=True,
                        interpret=False)
    want = sample_tokens(lg, temps, key, tags, counters, use_pallas=False)
    same = bool(jnp.array_equal(got, want))
    print(f"kernel sampling ({slots}x{vocab}): identical={same}")
    _check(same, "sampling differs from its reference")


def phase_train(seed: int, steps: int = 3, reduced: bool = False):
    import jax

    from repro.configs import get_config, reduced_for_smoke
    from repro.core.timefloats import TFConfig
    from repro.data.pipeline import DataPipeline
    from repro.launch import train
    from repro.optim.optimizers import OptimizerConfig
    from repro.train import step as tsl

    rc, text = _run_capturing(train.main, [
        "--arch", ARCH, "--steps", str(steps), "--batch", "8", "--seq",
        "256", "--seed", str(seed), "--log-every", "1",
        *(["--reduced"] if reduced else [])])
    losses = _losses(text)
    print(f"train launcher: rc={rc} losses={losses}")
    _check(rc == 0 and len(losses) == steps
           and all(math.isfinite(v) for v in losses),
           "launch/train.py did not give a finite loss every step")

    cfg = get_config(ARCH)
    if reduced:
        cfg = reduced_for_smoke(cfg)
    tcfg = tsl.TrainConfig(optimizer=OptimizerConfig(
        name="adamw", lr=3e-4, total_steps=steps))
    # The launcher's data for step 0.
    batch = DataPipeline(cfg, batch=8, seq=256, seed=seed,
                         kind="markov" if cfg.vocab_size <= 65536
                         else "lm").batch_at(0)
    mode_loss = {}
    for mode in ("pallas", "separable"):
        c = dataclasses.replace(cfg, tf=TFConfig(mode=mode))
        step = jax.jit(tsl.make_train_step(c, tcfg), donate_argnums=(0,))
        # Keep only the metrics: one full-width state (params + AdamW,
        # 7.15 GB) on the chip at a time.
        metrics = step(tsl.init_state(c, tcfg, jax.random.PRNGKey(seed)),
                       batch)[1]
        mode_loss[mode] = float(metrics["loss"])
    rel = (abs(mode_loss["pallas"] - mode_loss["separable"])
           / abs(mode_loss["separable"]))
    print(f"train one step: pallas {mode_loss['pallas']!r} separable "
          f"{mode_loss['separable']!r} rel diff {rel:.3g} "
          f"(tol {TRAIN_MODE_RTOL})")
    _check(math.isfinite(mode_loss["pallas"]) and rel <= TRAIN_MODE_RTOL,
           "pallas and separable losses disagree")


def phase_serve(seed: int, reduced: bool = False):
    from repro.kernels import dispatch
    from repro.launch import serve

    OUT.mkdir(parents=True, exist_ok=True)
    argv = ["--arch", ARCH, "--paged", "--prefix-len", "32", "--requests",
            "8", "--slots", "4", "--max-new", "16", "--max-len", "128",
            "--temperature", "0", "--seed", str(seed),
            *(["--reduced"] if reduced else [])]
    streams = {}
    for name, use_pallas in (("pallas", None), ("reference", False)):
        path = OUT / f"serve_tokens_{name}.json"
        with dispatch.override(use_pallas=use_pallas):
            rc = serve.main(argv + ["--tokens-out", str(path)])
        gc.collect()
        streams[name] = json.loads(path.read_text())
        print(f"serve ({name} kernels): rc={rc} "
              f"served={len(streams[name])}/8")
        _check(rc == 0 and len(streams[name]) == 8,
               f"serve ({name}) failed or left requests unserved")
    same = total = 0
    for uid, want in streams["reference"].items():
        got = streams["pallas"][uid]
        total += max(len(got), len(want))
        same += sum(a == b for a, b in zip(got, want))
    share = same / max(total, 1)
    print(f"serve token identity: {same}/{total} = {share:.4f} "
          f"(gate {TOKEN_IDENTITY_MIN})")
    _check(share >= TOKEN_IDENTITY_MIN, "paged serve tokens diverge")


def phase_four_chips(seed: int, steps: int = 3, reduced: bool = False):
    """Sharded train through launch/train.py vs one chip of the host."""
    from repro.launch import train

    base = ["--arch", ARCH, "--steps", str(steps), "--batch", "8", "--seq",
            "256", "--seed", str(seed), "--log-every", "1",
            *(["--reduced"] if reduced else [])]
    runs = {}
    for mesh in ("2x2", "1"):
        rc, text = _run_capturing(train.main, base + ["--mesh", mesh])
        m = re.search(r"state bytes: total (\d+), per device \[([\d, ]+)\]",
                      text)
        _check(rc == 0 and m is not None, f"train --mesh {mesh} failed")
        runs[mesh] = (_losses(text), int(m.group(1)),
                      [int(v) for v in m.group(2).split(",")])
    (l4, total, per_dev), (l1, _, _) = runs["2x2"], runs["1"]
    print(f"four chips: losses 2x2 {l4} vs one chip {l1}")
    print(f"four chips: state bytes total {total}, per device {per_dev} "
          f"(shares {[round(b / total, 4) for b in per_dev]})")
    _check(len(l4) == len(l1) == steps, "missing step losses")
    _check(all(abs(a - b) <= SHARDED_RTOL * abs(b) for a, b in zip(l4, l1)),
           "sharded losses disagree with one chip")
    # FSDP on embed x TP on heads/ffw/vocab: each device holds about a
    # quarter; the replicated remainder (norms, step counters) is small.
    _check(len(per_dev) == 4
           and all(0.2 <= b / total <= 0.35 for b in per_dev),
           "state is not spread over the four devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded train phase, on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    device = phase_device(jax, 4 if args.four_chips else 1)
    use_compile_cache()
    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("kernels", phase_kernels), ("train", phase_train),
               ("serve", phase_serve)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(args.seed)
        print(f"phase {name} passed in {time.perf_counter() - t0:.1f} s "
              f"(host wall clock, compiles included)", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
