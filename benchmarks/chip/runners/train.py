"""The runner of mixes of kind ``train``: the program's train step (``repro.train.step``), jitted
and sharded as ``launch/train.py`` builds it, fed by the program's
``DataPipeline``.

Set-up builds the one jitted step with its state and drives it through
the first ``check_steps`` steps, reading what the check needs on the way
(each step's loss, the first gradient from AdamW's first moment, the
parameters' change); the window then goes on with that same step and
state. After the window the reference follows the same first steps.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference, spec, tracing, weights, work
from benchmarks.chip.measure import (TRACE_DIR, GcWatch, Profiler,
                                     compile_counter, memory_peak)

now = time.perf_counter


def _opt(mix: dict) -> dict:
    return {k: mix["optimizer"][k] for k in
            ("lr", "warmup", "total_steps", "b1", "b2", "eps", "grad_clip",
             "min_lr_ratio")}


def data_seed(seed: int) -> int:
    """The data pipeline's 31-bit seed, drawn from every bit of ``seed``."""
    digest = hashlib.blake2b(str(seed).encode(), digest_size=4).digest()
    return int.from_bytes(digest, "little") & 0x7FFFFFFF


def feed(cfg, mix: dict, seed: int):
    """(mesh, sharding rules, data pipeline) of a cell."""
    from repro.data.pipeline import DataPipeline
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as shd

    dims = tuple(mix["mesh"])
    names = ("data", "model")[: len(dims)] if len(dims) > 1 else ("data",)
    mesh = make_mesh(dims, names, devices=jax.devices()[:math.prod(dims)])
    rules = shd.make_rules(mesh)
    pipe = DataPipeline(cfg, batch=mix["batch"], seq=mix["seq"],
                        seed=data_seed(seed), kind=mix["data"])
    pipe.shardings = shd.batch_shardings(pipe.batch_at(0), mesh, rules)
    return mesh, rules, pipe


def build(cfg, arch, mix: dict, seed: int):
    """(jitted step, the step before jit, state, pipeline, mesh, param
    shardings) for a cell whose weights the architecture module ``arch``
    plans."""
    from repro.optim.optimizers import OptimizerConfig, make_optimizer
    from repro.parallel import sharding as shd
    from repro.train import step as tsl

    o = _opt(mix)
    tcfg = tsl.TrainConfig(optimizer=OptimizerConfig(
        name="adamw", lr=o["lr"], warmup=o["warmup"],
        total_steps=o["total_steps"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        grad_clip=o["grad_clip"]))
    mesh, rules, pipe = feed(cfg, mix, seed)
    optimizer = make_optimizer(tcfg.optimizer)

    def shapes(_):
        p = jax.eval_shape(lambda: weights.make_params(cfg, 0, arch))
        return tsl.TrainState(step=jax.ShapeDtypeStruct((), jnp.int32),
                              params=p, opt=jax.eval_shape(optimizer.init, p),
                              rng=jax.ShapeDtypeStruct((2,), jnp.uint32))

    s_shard = shd.tree_shardings(tsl.state_axes(cfg, tcfg), shapes(None),
                                 mesh, rules)
    params = weights.make_params(cfg, seed, arch, shardings=s_shard.params)
    opt = jax.jit(optimizer.init, out_shardings=s_shard.opt)(params)
    state = tsl.TrainState(step=jax.device_put(jnp.zeros((), jnp.int32),
                                               s_shard.step),
                           params=params, opt=opt,
                           rng=jax.device_put(weights.seed_key(seed, 2),
                                              s_shard.rng))
    step_fn = tsl.make_train_step(cfg, tcfg)

    def fn(s, b):
        with shd.sharding_context(mesh, rules):
            return step_fn(s, b)

    jitted = jax.jit(fn, in_shardings=(s_shard, pipe.shardings),
                     donate_argnums=(0,))
    return jitted, fn, state, pipe, mesh, s_shard.params


def _norms(tree):
    return jax.device_get(jax.jit(reference.leaf_norms)(tree))


def _paths(tree):
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def compare(prog: dict, ref: dict, cut: float) -> dict:
    """The gaps: the loss of each step, relative to the reference's; and,
    leaf by leaf, the gap between the program's and the reference's norm
    of the first gradient and of the change, relative to the larger of the
    reference's norm of that leaf and of the median leaf. A token-indexed
    table's change is read over the rows of the tokens that the check
    steps hold. Leaves whose reference gradient is under ``cut`` of the
    median leaf's move by round-off alone and are left out of the change.
    ``worst`` names the leaf that sets each leaf gap; ``rest`` gives each
    table's change over the rows of absent tokens (program, reference),
    which is read and not compared."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                       ref["losses"]))
    paths = _paths(ref["grad"])
    gp, gr = (jax.tree.leaves(prog["grad"]), jax.tree.leaves(ref["grad"]))
    cp, cr = (jax.tree.leaves(prog["change"]),
              jax.tree.leaves(ref["change"]))
    rp, rr = (jax.tree.leaves(prog["rest"]), jax.tree.leaves(ref["rest"]))
    gr_med = float(np.median(gr))
    g_gaps = [abs(float(a) - float(b)) / max(float(b), gr_med)
              for a, b in zip(gp, gr)]
    moved = [i for i, b in enumerate(gr) if float(b) >= cut * gr_med]
    cr_med = float(np.median([cr[i] for i in moved]))
    c_gaps = {i: abs(float(cp[i]) - float(cr[i])) / max(float(cr[i]), cr_med)
              for i in moved}
    gi = int(np.argmax(g_gaps))
    ci = max(c_gaps, key=c_gaps.get)
    rest = {paths[i]: [float(rp[i]), float(rr[i])] for i in range(len(rr))
            if float(rr[i]) != float(cr[i]) or float(rp[i]) != float(cp[i])}
    return {"loss_gap": loss_gap, "grad_gap": g_gaps[gi],
            "change_gap": c_gaps[ci],
            "change_gap_median": float(np.median(list(c_gaps.values()))),
            "leaves_out": len(gr) - len(moved),
            "worst": {"grad": [paths[gi], float(gp[gi]), float(gr[gi])],
                      "change": [paths[ci], float(cp[ci]), float(cr[ci])]},
            "rest": rest}


READ = ("loss_gap", "grad_gap", "change_gap", "change_gap_median")


def checks(gaps: dict, finite: bool, limits: dict) -> dict:
    """{number: (value, limit, relation)} for every number the cell's
    limits name, and the loss being finite."""
    out = {"finite_loss": (int(finite), 1, ">=")}
    out.update({k: (gaps[k], limits[k], "<=") for k in READ if k in limits})
    return out


def reference_steps(cell, cfg, seed: int, batches, rows, p_shard=None,
                    **kw):
    """The reference over the check steps, from the seed's weights: the
    forward pass of the cell's architecture module, at its sizes."""
    arch = cell.arch
    got = reference.train_steps(
        lambda: weights.make_params(cfg, seed, arch, shardings=p_shard),
        batches, arch.forward, arch.model_spec(cell.config),
        _opt(cell.traffic), rows, **kw)
    return dict(zip(("losses", "grad", "change", "rest"), got))


def report(gaps: dict, prog: dict, ref: dict, out=sys.stderr):
    print(f"losses: program {prog['losses']} reference {ref['losses']}",
          file=out)
    for k, (path, p, r) in gaps["worst"].items():
        print(f"widest {k} gap at {path}: program {p!r} reference {r!r}",
              file=out)
    for path, (p, r) in gaps["rest"].items():
        print(f"change of {path} over absent tokens' rows (not compared): "
              f"program {p!r} reference {r!r}", file=out)
    for k in READ:
        print(f"reading {k}: {gaps[k]!r}", file=out)


def run(cell, seed, seconds, trace, *, device, chips, t_start, fault=None):
    """One run of a training cell: set-up with the first steps, the
    window, the check. ``fault`` (tests) takes the step before jit and
    returns the jitted step the run uses."""
    mix = cell.traffic
    cfg = spec.model_config(cell.config)
    jitted, fn, state, pipe, mesh, p_shard = build(cfg, cell.arch, mix, seed)
    if fault is not None:
        jitted = fault(fn)
    n_check = int(mix["check_steps"])
    b1 = float(mix["optimizer"]["b1"])
    batches = [jax.device_get(pipe.batch_at(i)) for i in range(n_check)]
    rows = reference.present_rows(batches, cfg.vocab_size)
    prog = {"losses": []}
    annotate = (jax.profiler.TraceAnnotation if trace
                else lambda name: contextlib.nullcontext())
    with jax.set_mesh(mesh):
        for i in range(n_check):
            state, m = jitted(state, pipe.batch_at(i))
            prog["losses"].append(float(m["loss"]))
            if i == 0:
                # AdamW's first moment after one step is (1 - b1) times
                # the (clipped) gradient it was given.
                prog["grad"] = jax.tree.map(lambda x: x / (1 - b1),
                                            _norms(state.opt["m"]))
        prog["change"], prog["rest"] = jax.device_get(reference.change_norms(
            state.params,
            weights.make_params(cfg, seed, cell.arch, shardings=p_shard),
            jnp.asarray(rows)))
        prof = Profiler(bool(trace), float(mix.get("trace_s", seconds)),
                        TRACE_DIR / cell.name)
        compiles = compile_counter()
        step = n_check
        times = []
        collector = GcWatch()
        collector.open()
        prof.open()
        compiles["on"] = True
        t0 = now()
        setup_s = t0 - t_start
        phases = []
        while True:
            ta = now()
            with annotate("bench.feed"):
                batch = pipe.batch_at(step)
            tb = now()
            with annotate("bench.step"):
                state, m = jitted(state, batch)
                tc = now()
                jax.block_until_ready(m["loss"])
            t = now()
            times.append(t)
            phases.append((tb - ta, tc - tb, t - tc))
            step += 1
            prof.maybe_close(t)
            if t - t0 >= seconds:
                break
        prof.close()
        compiles["on"] = False
        collector.close()
    print(f"compiles in the window: {compiles['n']}", file=sys.stderr)
    print(collector.summary(), file=sys.stderr)
    took = np.diff([t0] + times)
    k = int(took.argmax())
    feed_s, call_s, wait_s = phases[k]
    print(f"window: {len(times)} steps, median {1e3 * np.median(took):.2f} "
          f"ms, slowest {1e3 * took.max():.2f} ms (step {k}: feed "
          f"{1e3 * feed_s:.2f}, call {1e3 * call_s:.2f}, wait "
          f"{1e3 * wait_s:.2f} ms)", file=sys.stderr)
    steps = len(times)
    tokens = steps * mix["batch"] * mix["seq"]
    e2e = {"train_tokens_per_s": tokens / (times[-1] - t0),
           "setup_s": setup_s}
    ctx = None
    if trace:
        n_tr = sum(1 for t in times if prof.lo <= t <= prof.hi)
        ctx = {"trace": tracing.load(str(prof.out)),
               "span_s": prof.hi - prof.lo,
               "flops": n_tr * cell.arch.train_flops(
                   cell.config, mix["batch"], mix["seq"]),
               "config": cell.config, "peaks": work.peaks(device["kind"]),
               "chips": chips}
    peak = memory_peak(chips)
    finite = bool(np.isfinite(float(m["loss"])))
    del state, jitted, m
    gc.collect()
    t_ref = now()
    with jax.set_mesh(mesh):
        ref = reference_steps(cell, cfg, seed, batches, rows, p_shard)
    print(f"reference: {now() - t_ref:.1f} s", file=sys.stderr)
    gaps = compare(prog, ref, float(cell.limits["leaf_cut"]))
    report(gaps, prog, ref)
    return dict(e2e=e2e, ctx=ctx, peak=peak, attempted=steps, failed=0,
                checks=checks(gaps, finite, cell.limits), gaps=gaps,
                ref=ref, batches=batches, rows=rows)
