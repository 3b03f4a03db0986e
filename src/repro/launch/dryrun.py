import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing import: jax locks the device count on
# first init. Only the dry-run sees 512 placeholder devices.
# (No `from __future__` here — these two lines must stay first.)

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json

Per cell this builds the jitted step (train_step / prefill forward /
decode_step), lowers against ShapeDtypeStructs (no allocation), compiles,
and records memory_analysis(), cost_analysis() and the collective-op bytes
parsed from the optimized HLO — the inputs to EXPERIMENTS.md §Dry-run /
§Roofline. Hardware model: TPU v5e-class (197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s ICI per chip-link).
"""
import argparse
import dataclasses
import json
import math
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.configs import shapes as shapes_lib
from repro.configs.base import ModelConfig
from repro.core.timefloats import TFConfig
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models.common import spec_shapes
from repro.optim.optimizers import OptimizerConfig
from repro.parallel import sharding as shd
from repro.train import step as train_step_lib

HW = {
    "peak_flops": 197e12,   # bf16 / chip
    "hbm_bw": 819e9,        # bytes/s / chip
    "ici_bw": 50e9,         # bytes/s / chip-link
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+((?:\()?[a-z0-9\[\],{}\s]+(?:\))?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")

# Effective wire-bytes factor per collective kind (ring algorithms).
_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op in the optimized
    (per-device SPMD) HLO, weighted by ring wire factors."""
    out: Dict[str, float] = {k: 0.0 for k in _COLL_FACTOR}
    out["total"] = 0.0
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_txt, kind, _start = m.group(1), m.group(2).lower(), m.group(3)
        b = _shape_bytes(shape_txt) * _COLL_FACTOR[kind]
        out[kind] += b
        out["total"] += b
    return out


# Per-arch training overrides for the big cells (optimizer-state budget).
TRAIN_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "kimi-k2-1t-a32b": dict(
        optimizer=OptimizerConfig(name="adafactor", grad_clip=1.0),
        accum_dtype="bfloat16", accum=64),
    "deepseek-v3-671b": dict(
        optimizer=OptimizerConfig(name="adafactor", grad_clip=1.0),
        accum_dtype="bfloat16", accum=64),
    "mistral-large-123b": dict(
        optimizer=OptimizerConfig(name="adafactor", grad_clip=1.0)),
}

# Model-config overrides for the >=100B cells: bf16 parameter storage
# (paired with adafactor above) keeps params+opt state inside 16 GB HBM.
MODEL_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "kimi-k2-1t-a32b": dict(param_dtype="bfloat16"),
    "deepseek-v3-671b": dict(param_dtype="bfloat16"),
    "mistral-large-123b": dict(param_dtype="bfloat16"),
}

# --variant opt: the beyond-paper §Perf configuration per architecture.
# Each entry: model-config overrides and/or logical->physical rule overrides
# (None values mean "replicate"). See EXPERIMENTS.md §Perf for the
# hypothesis -> measurement trail behind every entry.
OPT_MODEL_OVERRIDES: Dict[str, Dict[str, Any]] = {
    # I-4: 56 heads % 16 != 0 -> pad q heads per kv group to 64 (exact,
    # output-masked) so attention shards over the model axis.
    "deepseek-coder-33b": dict(head_pad_to=64),
}
OPT_RULES_OVERRIDES: Dict[str, Dict[str, tuple]] = {
    # I-3: sub-2B models — model parallelism is pure overhead at d<=2048;
    # use the whole mesh as data parallelism (weights replicated, embed
    # FSDP over data only).
    "qwen3-0.6b": {"batch": ("pod", "data", "model"), "heads": (),
                   "kv_heads": (), "ffw": (), "vocab": (), "inner": (),
                   "head_dim_cache": (), "kv_lora_cache": ()},
    "hymba-1.5b": {"batch": ("pod", "data", "model"), "heads": (),
                   "kv_heads": (), "ffw": (), "vocab": (), "inner": (),
                   "head_dim_cache": (), "kv_lora_cache": ()},
    "mamba2-1.3b": {"batch": ("pod", "data", "model"), "heads": (),
                    "kv_heads": (), "ffw": (), "vocab": (), "inner": (),
                    "head_dim_cache": (), "kv_lora_cache": ()},
}


# I-3 companion: with the whole mesh on data parallelism the global batch
# (256) maps 1 seq/device — grad accumulation becomes pure overhead.
OPT_TRAIN_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "qwen3-0.6b": dict(accum=1),
    "hymba-1.5b": dict(accum=1),
    "mamba2-1.3b": dict(accum=1),
}


def _opt_moe_chunk(cfg: ModelConfig, cell) -> ModelConfig:
    """I-5: chunk the MoE dispatch so one (E, C_chunk, D) buffer is alive at
    a time — bounds the 32k-prefill working set."""
    if cfg.moe is None:
        return cfg
    tokens = cell.global_batch * cell.seq_len
    if cell.kind == "train":
        tokens = tokens // 64 if cfg.moe else tokens  # accum=64 microbatch
    chunk = 16384
    if tokens <= chunk:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch_chunk=chunk))


def _model_cfg(arch: str, quant: str) -> ModelConfig:
    cfg = get_config(arch, **MODEL_OVERRIDES.get(arch, {}))
    if quant == "none":
        cfg = dataclasses.replace(cfg, quant="none")
    elif quant == "timefloats":
        cfg = dataclasses.replace(cfg, quant="timefloats",
                                  tf=TFConfig(mode="separable"))
    else:
        raise ValueError(quant)
    return cfg


def _train_cfg(arch: str, multi_pod: bool, accum: Optional[int]) -> train_step_lib.TrainConfig:
    over = dict(TRAIN_OVERRIDES.get(arch, {}))
    if accum is None:
        accum = over.pop("accum", 8 if multi_pod else 16)
    else:
        over.pop("accum", None)
    return train_step_lib.TrainConfig(accum=accum, **over)


def build_cell(arch: str, shape: str, mesh, *, quant: str = "timefloats",
               accum: Optional[int] = None, variant: str = "baseline"):
    """Returns (jitted_fn, arg_sds: tuple, donate) ready to .lower()."""
    multi_pod = "pod" in mesh.shape
    cfg = _model_cfg(arch, quant)
    cell = shapes_lib.CELLS[shape]
    rule_over = None
    if variant == "opt":
        if arch in OPT_MODEL_OVERRIDES:
            cfg = dataclasses.replace(cfg, **OPT_MODEL_OVERRIDES[arch])
        cfg = _opt_moe_chunk(cfg, cell)
        rule_over = OPT_RULES_OVERRIDES.get(arch)
    rules = shd.make_rules(mesh, overrides=rule_over)
    p_axes = model_lib.param_axes(cfg)
    p_shapes = jax.eval_shape(lambda k: model_lib.init(cfg, k),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shard = shd.tree_shardings(p_axes, p_shapes, mesh, rules)

    if cell.kind == "train":
        if variant == "opt" and accum is None and arch in OPT_TRAIN_OVERRIDES:
            accum = OPT_TRAIN_OVERRIDES[arch].get("accum")
        tcfg = _train_cfg(arch, multi_pod, accum)
        state_sds = jax.eval_shape(
            lambda k: train_step_lib.init_state(cfg, tcfg, k),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        s_axes = train_step_lib.state_axes(cfg, tcfg)
        s_shard = shd.tree_shardings(
            jax.tree.map(lambda a: a, s_axes,
                         is_leaf=lambda x: isinstance(x, tuple)),
            state_sds, mesh, rules)
        batch_sds = shapes_lib.train_batch_specs(cfg, cell)
        b_shard = shd.batch_shardings(batch_sds, mesh, rules)
        step_fn = train_step_lib.make_train_step(cfg, tcfg)

        def fn(state, batch):
            with shd.sharding_context(mesh, rules):
                return step_fn(state, batch)

        jitted = jax.jit(fn, in_shardings=(s_shard, b_shard),
                         donate_argnums=(0,))
        return jitted, (state_sds, batch_sds)

    if cell.kind == "prefill":
        batch_sds = shapes_lib.prefill_specs(cfg, cell)
        b_shard = shd.batch_shardings(batch_sds, mesh, rules)

        def fn(params, batch):
            with shd.sharding_context(mesh, rules):
                logits, _ = model_lib.forward(params, batch, cfg, train=False)
                return jnp.argmax(logits[:, -1], axis=-1)

        jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
        return jitted, (p_shapes, batch_sds)

    # decode / long_decode
    cache_sds, tok_sds = shapes_lib.decode_specs(cfg, cell)
    c_axes = model_lib.cache_axes(cfg)
    c_shard = shd.tree_shardings(c_axes, cache_sds, mesh, rules)
    t_shard = shd.batch_shardings({"t": tok_sds}, mesh, rules)["t"]

    def fn(params, cache, tokens):
        with shd.sharding_context(mesh, rules):
            return model_lib.decode_step(params, cache, tokens, cfg)

    jitted = jax.jit(fn, in_shardings=(p_shard, c_shard, t_shard),
                     donate_argnums=(1,))
    return jitted, (p_shapes, cache_sds, tok_sds)


def analyze(compiled, n_devices: int) -> Dict[str, Any]:
    """Roofline terms from the compiled artifact.

    Primary numbers come from the trip-count-aware HLO census
    (launch/hlo_census.py): XLA's cost_analysis() counts every while body
    exactly once, undercounting scans (layers × accum microbatches) by
    orders of magnitude (§Roofline methodology note). The raw cost_analysis
    values are retained for reference.
    """
    from repro.launch import hlo_census

    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    cen = hlo_census.census(hlo)
    flops = cen["flops"]
    bytes_acc = cen["bytes"]
    bytes_dot = cen["bytes_dot"]
    coll = cen["collective"]
    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        mem_rec[attr] = getattr(mem, attr, None)
    t_c = flops / HW["peak_flops"]
    t_m = bytes_acc / HW["hbm_bw"]
    t_x = coll["total"] / HW["ici_bw"]
    dominant = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
                   key=lambda kv: kv[1])[0]
    return {
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "bytes_dot_per_device": bytes_dot,
        "t_memory_dot_s": bytes_dot / HW["hbm_bw"],
        "collective_bytes_per_device": coll,
        "census_warnings": cen["warnings"][:5],
        "raw_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "memory": mem_rec,
        "t_compute_s": t_c,
        "t_memory_s": t_m,
        "t_collective_s": t_x,
        "dominant": dominant,
        "n_devices": n_devices,
    }


def model_flops(arch: str, shape: str, quant: str) -> Dict[str, float]:
    cfg = _model_cfg(arch, quant)
    cell = shapes_lib.CELLS[shape]
    n = cfg.param_count()
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * shapes_lib.text_len(cfg, cell)
        factor = 6.0
    elif cell.kind == "prefill":
        tokens = cell.global_batch * shapes_lib.text_len(cfg, cell)
        factor = 2.0
    else:
        tokens = cell.global_batch  # one token per sequence
        factor = 2.0
    return {"params": n, "active_params": n_active,
            "model_flops": factor * n_active * tokens, "tokens": tokens}


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             quant: str = "timefloats", accum: Optional[int] = None,
             variant: str = "baseline") -> Dict[str, Any]:
    cfg = get_config(arch)
    cell = shapes_lib.CELLS[shape]
    ok, reason = shapes_lib.applicable(cfg, cell)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape, "quant": quant, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    if not ok:
        rec["status"] = reason
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    jitted, args = build_cell(arch, shape, mesh, quant=quant, accum=accum,
                              variant=variant)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    rec.update(analyze(compiled, mesh.size))
    rec.update(model_flops(arch, shape, quant))
    hlo_flops_global = rec["flops_per_device"] * mesh.size
    rec["useful_flops_ratio"] = (rec["model_flops"] / hlo_flops_global
                                 if hlo_flops_global else 0.0)
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["status"] = "ok"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(shapes_lib.CELLS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant", default="timefloats",
                    choices=["timefloats", "none"])
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt"])
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    archs = list(ARCHS) if args.all or not args.arch else [args.arch]
    shapes = (list(shapes_lib.CELLS) if args.all or not args.shape
              else [args.shape])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r.get("quant"),
             r.get("variant", "baseline")) for r in results}

    for a, s, mp in cells:
        key = (a, s, "2x16x16" if mp else "16x16", args.quant, args.variant)
        if key in done:
            print(f"[skip cached] {key}")
            continue
        print(f"=== {a} × {s} × {key[2]} (quant={args.quant}, "
              f"variant={args.variant}) ===", flush=True)
        try:
            rec = run_cell(a, s, multi_pod=mp, quant=args.quant,
                           accum=args.accum, variant=args.variant)
        except Exception as e:  # record failures; they are bugs to fix
            rec = {"arch": a, "shape": s, "mesh": key[2], "quant": args.quant,
                   "variant": args.variant,
                   "status": f"FAIL: {type(e).__name__}: {e}"}
        results.append(rec)
        print(json.dumps(rec, indent=1, default=str), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1, default=str)
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"done: {n_ok}/{len(results)} ok")


if __name__ == "__main__":
    main()
