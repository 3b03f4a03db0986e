"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --batch 32 --seq 1024 --steps 1000 --mesh 4x2 --ckpt-dir /ckpt

On a real TPU pod each host runs this same script (jax.distributed
initializes from the TPU environment); on CPU, --fake-devices N builds a
placeholder mesh for integration testing. The mesh is (data, model) per pod
and (pod, data, model) with --multi-pod; sharding comes from the logical-
axis rules (parallel/sharding.py), fault tolerance from train/trainer.py
(atomic keep-N checkpoints, auto-resume, straggler watchdog, deterministic
restartable data).
"""
import argparse
import dataclasses
import math
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--quant", default="timefloats",
                    choices=["timefloats", "none"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["sgd", "adamw", "adafactor"])
    ap.add_argument("--insitu", action="store_true",
                    help="paper-faithful E4M4 in-situ weight updates")
    ap.add_argument("--mesh", default="",
                    help="DxM (e.g. 4x2) or PxDxM; empty = all devices on data")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="CPU placeholder devices (set before jax import)")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced (smoke) config of the chosen arch")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome trace-event JSON of the "
                         "run (DESIGN §11; load at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry snapshot (.json = "
                         "flat dict, else Prometheus text)")
    args = ap.parse_args(argv)

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax

    from repro.configs import get_config, reduced_for_smoke
    from repro.core.timefloats import TFConfig
    from repro.data.pipeline import DataPipeline
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.optim.optimizers import OptimizerConfig
    from repro.parallel import sharding as shd
    from repro.train import step as tsl
    from repro.train.trainer import LoopConfig, run_loop

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_for_smoke(cfg)
    cfg = dataclasses.replace(cfg, quant=args.quant)

    tcfg = tsl.TrainConfig(
        accum=args.accum,
        optimizer=OptimizerConfig(
            name=args.optimizer, lr=args.lr, total_steps=args.steps,
            insitu=TFConfig() if args.insitu else None))

    # ---- mesh ----
    n_dev = len(jax.devices())
    if args.mesh:
        dims = tuple(int(d) for d in args.mesh.split("x"))
        names = {1: ("data",), 2: ("data", "model"),
                 3: ("pod", "data", "model")}[len(dims)]
    else:
        dims, names = (n_dev,), ("data",)
    # A mesh smaller than the host (--mesh 1 on four chips) takes the first
    # devices.
    mesh = make_mesh(dims, names, devices=jax.devices()[:math.prod(dims)])
    rules = shd.make_rules(mesh)
    print(f"mesh {dict(zip(names, dims))} over {n_dev} devices; "
          f"arch={args.arch} quant={args.quant} "
          f"params={cfg.param_count() / 1e6:.1f}M")

    # ---- state + shardings ----
    state = tsl.init_state(cfg, tcfg, jax.random.PRNGKey(args.seed))
    s_axes = tsl.state_axes(cfg, tcfg)
    s_shard = shd.tree_shardings(s_axes, jax.tree.map(lambda a: a, state),
                                 mesh, rules)
    state = jax.device_put(state, s_shard)
    per_dev = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            per_dev[shard.device] += shard.data.nbytes
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    print(f"state bytes: total {total}, per device "
          f"{[per_dev[d] for d in mesh.devices.flat]}")

    pipe = DataPipeline(cfg, batch=args.batch, seq=args.seq, seed=args.seed,
                        kind="markov" if cfg.vocab_size <= 65536 else "lm")
    b0 = pipe.batch_at(0)
    b_shard = shd.batch_shardings(b0, mesh, rules)
    pipe.shardings = b_shard

    step_fn = tsl.make_train_step(cfg, tcfg)

    def fn(s, b):
        with shd.sharding_context(mesh, rules):
            return step_fn(s, b)

    jitted = jax.jit(fn, in_shardings=(s_shard, b_shard),
                     donate_argnums=(0,))

    # Digital-twin telemetry (DESIGN.md §6): placement + trace census once,
    # then per-step energy/write counters ride the metrics stream.
    hw_monitor = None
    if args.quant == "timefloats":
        from repro.hw.schedule import HwMonitor

        hw_monitor = HwMonitor.for_training(state.params, b0, cfg)
        pl = hw_monitor.placement
        print(f"hw twin: {pl.tiles} tiles / {pl.macros} macros "
              f"(util {pl.utilization:.1%}), "
              f"{hw_monitor.step_schedule.energy_pj / 1e6:.2f} uJ/step, "
              f"{hw_monitor.step_schedule.cells_written} cell writes/step")

    def on_metrics(step, m):
        hw = (f" hw {m['hw_step_energy_uj']:.2f}uJ"
              if "hw_step_energy_uj" in m else "")
        print(f"step {step:5d} loss {m['loss']:.4f} gnorm "
              f"{m['grad_norm']:.2f}{hw}", flush=True)

    tracer = None
    registry = None
    if args.trace_out or args.metrics_out:
        from repro.obs.metrics import MetricsRegistry
        from repro.obs.trace import Tracer

        tracer = Tracer() if args.trace_out else None
        registry = MetricsRegistry()

    loop = LoopConfig(total_steps=args.steps, log_every=args.log_every,
                      ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir)
    with jax.set_mesh(mesh):
        state, report = run_loop(state, jitted, pipe.batch_at, loop,
                                 restore_shardings=s_shard,
                                 on_metrics=on_metrics,
                                 hw_monitor=hw_monitor,
                                 tracer=tracer,
                                 metrics_registry=registry)
    print(f"done: steps={report.steps_run} resumed_from="
          f"{report.resumed_from} stragglers={report.straggler_events} "
          f"final_loss={report.losses[-1]:.4f}")
    if report.hw is not None:
        print(f"hw twin totals: {report.hw['total_energy_j']:.3e} J, "
              f"{report.hw['total_cell_writes']:.3g} cell writes, "
              f"endurance used {report.hw['endurance_frac']:.2e}")
    if args.metrics_out:
        from repro.obs.export import write_metrics

        write_metrics(args.metrics_out, registry)
        print(f"metrics written to {args.metrics_out}")
    if args.trace_out:
        from repro.obs.export import write_chrome_trace

        payload = write_chrome_trace(
            args.trace_out, tracer,
            metadata={"hw": report.hw, "arch": args.arch})
        print(f"trace written to {args.trace_out} "
              f"({payload['metadata']['events']} events, "
              f"{payload['metadata']['dropped']} dropped)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
