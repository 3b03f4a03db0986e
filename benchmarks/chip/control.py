"""The readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \\
        [--fault-seeds 1,2] --seconds <s>

For each seed, in one process: a run of the cell as the benchmark makes
it (the program's readings). For each of ``--fault-seeds`` besides: the
control, the plain reference put in the program's place one precision
step below the configuration's (its TimeFloats scalar products in E4M3,
3-bit mantissas, where the configuration states E4M4), and the fault
"half of the batch left out, the mean taken over the rest", planted in
the reference. Each is compared with the sound reference and judged
against the cell's limits as a run is, so ``correct`` has to come out
false for both. A step that returns its state unchanged reads a change gap
of 1 by the measure and needs no run. One JSON line per seed. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parents[2])]

from benchmarks.chip import run  # noqa: E402

CONTROL_MAN_BITS = 3


def _judged(gaps: dict, limits: dict) -> dict:
    from benchmarks.chip.runners import train

    got = train.checks(gaps, True, limits)
    return {"correct": run.judge(got),
            **{k: gaps[k] for k in train.READ}}


def training_control(cell, seed: int, res: dict) -> dict:
    """The control and the half-batch fault of a training cell, each
    judged against the cell's limits. ``res`` is the program's run, whose
    reference, batches and row mask they reuse."""
    import jax

    from benchmarks.chip import spec
    from benchmarks.chip.runners import train

    cfg = spec.model_config(cell.config)
    mesh, _rules, _pipe = train.feed(cfg, cell.traffic, seed)
    batches, rows, ref = res["batches"], res["rows"], res["ref"]
    half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
            for b in batches]
    cut = float(cell.limits["leaf_cut"])
    out = {}
    with jax.set_mesh(mesh):
        for name, kw, bs in (("control", {"man_bits": CONTROL_MAN_BITS},
                              batches), ("half_batch", {}, half)):
            got = train.reference_steps(cell, cfg, seed, bs, rows, **kw)
            gaps = train.compare(got, ref, cut)
            train.report(gaps, got, ref)
            out[name] = _judged(gaps, cell.limits)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    run.CACHE_DIR.mkdir(exist_ok=True)
    import jax

    from benchmarks.chip import spec
    from repro.launch.compile_cache import use_compile_cache

    cell = spec.load_cell(args.workload)
    device = run.device_info(cell.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = spec.runner(cell.traffic["kind"]).run(
            cell, seed, args.seconds, False, device=device,
            chips=cell.chips, t_start=run.T_START)
        line = {"seed": seed, "program": _judged(res["gaps"], cell.limits)}
        line["program"]["correct"] = run.judge(res["checks"])
        line["metrics"] = res["e2e"]
        if seed in faults:
            line.update(training_control(cell, seed, res))
        print(json.dumps(line), flush=True)
        del res
    return 0


if __name__ == "__main__":
    sys.exit(main())
