"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

One process: make the weights from the seed, build the program's train
step, warm every shape the cell uses (set-up), measure for
``--seconds``, then check what the timed path produced against the plain
reference. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
traced, ``breakdown``), and last of all ``checks``: each compared number
beside its limit. The same checks are the last lines on standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the device and reports its per-layer metrics. A machine without a TPU, or
with fewer chips than the cell asks for, gets no result and exit code 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# JAX's persistent compilation cache lives in the checkout, at a fixed
# path, whatever the environment names: two checkouts never share one, and
# every run of a cell after the first finds its programs there. The
# program's use_compile_cache() takes the directory from this variable.
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def _paths():
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"the program (src/repro) is not in {ROOT}")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{d.platform} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def judge(checks: dict) -> bool:
    ok = True
    for value, limit, rel in checks.values():
        good = value >= limit if rel == ">=" else value <= limit
        ok = ok and good and not (isinstance(value, float)
                                  and math.isnan(value))
    return ok


def result_line(cell, device, res, trace: bool) -> dict:
    from benchmarks.chip import spec, tracing

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = res["e2e"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = spec.reader(m["name"])(res["ctx"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device)
    dev["memory_peak_bytes"] = res["peak"]
    out = {"correct": judge(res["checks"]),
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": dev}
    if trace:
        tr = res["ctx"]["trace"]
        dev["busy_s"] = tracing.busy_s(tr)
        dev["window_s"] = res["ctx"]["span_s"]
        out["breakdown"] = {"device_ops": tracing.top_ops(tr),
                            "idle_gaps": tracing.idle_gaps(tr)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim, _rel) in res["checks"].items()}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, **kw) -> dict:
    """One run of ``cell``, by the runner of its mix's kind."""
    from benchmarks.chip import spec

    device = device_info(cell.chips, require_chip)
    res = spec.runner(cell.traffic["kind"]).run(
        cell, seed, seconds, trace, device=device, chips=cell.chips,
        t_start=T_START, **kw)
    return result_line(cell, device, res, trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    CACHE_DIR.mkdir(exist_ok=True)
    try:
        _paths()
        from benchmarks.chip import spec

        cell = spec.load_cell(args.workload)
        device_info(cell.chips)
    except (FileNotFoundError, KeyError, NoChip) as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # Cache every program, small ones too, so that a second run of a cell
    # compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
