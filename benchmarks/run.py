"""Benchmark orchestrator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table1 fig7

Prints ``name,value,note`` CSV lines (the harness contract) and a summary,
and writes every record to ``BENCH_kernel.json`` (machine-readable: step
times, cache speedups, hw-report headline numbers) so the perf trajectory
is tracked across PRs instead of only printed.
"""
from __future__ import annotations

import json
import os
import platform
import sys
import time
import traceback

from benchmarks import (ablation_formats, fig3_linearity, fig7_variability,
                        hw_projection, kernel_bench, paged_attn_bench,
                        roofline, serve_bench, table1_energy,
                        table2_comparison)
from repro.launch.compile_cache import use_compile_cache

MODULES = {
    "table1": table1_energy,
    "table2": table2_comparison,
    "fig3": fig3_linearity,
    "fig7": fig7_variability,
    "kernel": kernel_bench,
    "paged_attn": paged_attn_bench,
    "formats": ablation_formats,
    "roofline": roofline,
    "hw": hw_projection,
    "serve": serve_bench,
}

JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernel.json")

# Headline records surfaced in the JSON summary (trajectory-over-PRs view).
SUMMARY_KEYS = (
    "kernel/step_cache_speedup_x",
    "kernel/scan_step_cache_speedup_x",
    "kernel/step_cached_us",
    "kernel/scan_step_cached_us",
    "table1/tops_per_watt",
    "hw/mlp_hardware_tops_per_watt",
    "hw/mlp_step_energy_uj",
    "hw/qwen3-0p6b_token_fwd_uj",
    "serve/fused_tok_per_s",
    "serve/speedup_x",
    "serve/prefix_hit_rate",
    "serve/prefix_paged_speedup_x",
    "serve/prefix_saved_pj",
    "serve/fused_paged_speedup_x",
    "serve/chunked_p95_ratio_x",
    "serve/chunked_tok_per_s_ratio",
    "serve/bursty_chunked_ttft_p95_s",
    "serve/obs_overhead_x",
    "serve/health_overhead_x",
    "serve/wear_parity",
    "serve/spec_speedup_x",
    "serve/spec_accept_rate",
    "serve/spec_pj_per_accepted_ratio",
    "kernel/paged_attn_gqa_speedup_x",
    "kernel/paged_attn_mla_speedup_x",
)

AUTOTUNE_PREFIX = "kernel/paged_attn_autotune/"

# ``--check`` regression gate: (direction, relative slack vs the committed
# baseline, absolute floor). Ratios only — raw wall-times are too noisy on
# shared CI boxes to gate; the ratio keys compare two paths measured in
# the same process, which is what stays stable.
CHECK_BANDS = {
    # "lower" keys gate a COST ratio: the absolute value is a ceiling
    # (tracing must stay within 5% of the untraced arm's tok/s).
    "serve/obs_overhead_x": ("lower", 0.5, 1.05),
    # Same contract for the streaming health monitor (DESIGN §13).
    "serve/health_overhead_x": ("lower", 0.5, 1.05),
    "serve/fused_paged_speedup_x": ("higher", 0.25, 1.3),
    # The stall-kill ratio is structurally ~10x but its magnitude is the
    # big-wave/chunk-step wall ratio, which moves with the host — a wide
    # relative band plus the PR's absolute 1.25x/0.9x acceptance floors.
    "serve/chunked_p95_ratio_x": ("higher", 0.6, 1.25),
    "serve/chunked_tok_per_s_ratio": ("higher", 0.3, 0.9),
    "serve/prefix_paged_speedup_x": ("higher", 0.25, 0.9),
    "serve/speedup_x": ("higher", 0.25, 1.0),
    # Speculative decoding (DESIGN §12): the tok/s win on the decode-heavy
    # motif scenario, and the energy overhead each ACCEPTED token carries
    # once rejected speculation is charged to it (~ (K+1)/mean-emit; the
    # ceiling allows acceptance dipping to ~1.8 emitted tokens/chain).
    "serve/spec_speedup_x": ("higher", 0.25, 1.5),
    "serve/spec_pj_per_accepted_ratio": ("lower", 0.3, 3.0),
    "kernel/paged_attn_gqa_speedup_x": ("higher", 0.25, 1.0),
    "kernel/paged_attn_mla_speedup_x": ("higher", 0.25, 1.0),
    "table1/tops_per_watt": ("higher", 0.05, 20.0),
}


def check_regressions(summary, baseline_summary) -> list:
    """Compare the fresh summary against the committed baseline.

    ``higher`` keys regress when they fall below ``(1 - slack) *
    baseline`` or below their absolute floor; ``lower`` keys (cost
    ratios) regress when they rise above ``(1 + slack) * baseline`` or
    above their absolute ceiling. Keys absent from either side are
    skipped (a module that didn't run keeps its old record via the
    merge)."""
    problems = []
    for key, (direction, slack, bound) in CHECK_BANDS.items():
        if key not in summary:
            continue
        val = float(summary[key])
        base = baseline_summary.get(key)
        if direction == "higher":
            if val < bound:
                problems.append(
                    f"{key}={val:.4g} below absolute floor {bound}")
                continue
            if base is not None and val < (1.0 - slack) * float(base):
                problems.append(f"{key}={val:.4g} regressed > {slack:.0%} "
                                f"vs baseline {float(base):.4g}")
        else:
            assert direction == "lower"
            if val > bound:
                problems.append(
                    f"{key}={val:.4g} above absolute ceiling {bound}")
                continue
            if base is not None and val > (1.0 + slack) * float(base):
                problems.append(f"{key}={val:.4g} regressed > {slack:.0%} "
                                f"vs baseline {float(base):.4g}")
    return problems


def main() -> None:
    check = "--check" in sys.argv[1:]
    picks = [a for a in sys.argv[1:] if a in MODULES] or list(MODULES)
    use_compile_cache()
    failures = []
    records = []
    print("name,value,note")
    for name in picks:
        mod = MODULES[name]
        t0 = time.time()

        def report(key, value, note="", module=name):
            records.append({"name": key, "value": value, "note": note,
                            "module": module})
            if isinstance(value, float):
                print(f"{key},{value:.6g},{note}")
            else:
                print(f"{key},{value},{note}")

        try:
            mod.run(report)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:  # keep going; report at the end
            failures.append((name, e))
            traceback.print_exc()

    # Merge with any existing file so a partial run (`run.py table1`) only
    # refreshes its own modules' records and never wipes the trajectory
    # the other modules last wrote. The pre-merge file is also the
    # committed baseline the --check gate compares against.
    baseline_summary = {}
    if os.path.exists(JSON_PATH):
        try:
            with open(JSON_PATH) as f:
                prev_payload = json.load(f)
            prev = prev_payload.get("records", [])
            baseline_summary = dict(prev_payload.get("summary", {}))
            records = [r for r in prev if r.get("module") not in picks] \
                + records
        except (json.JSONDecodeError, OSError):
            pass  # corrupt/unreadable previous file: rewrite from scratch
    by_name = {r["name"]: r["value"] for r in records}
    payload = {
        "schema": "timefloats-bench/v1",
        "modules_run": picks,
        "platform": {"python": platform.python_version(),
                     "machine": platform.machine()},
        "summary": {k: by_name[k] for k in SUMMARY_KEYS if k in by_name},
        # Split-K winners consumed by repro.kernels.autotune.best_n_splits
        # (the serve-time cache); rebuilt from the merged records so a run
        # without the paged_attn module keeps the committed values.
        "paged_attn_autotune": {
            r["name"][len(AUTOTUNE_PREFIX):]: int(r["value"])
            for r in records if r["name"].startswith(AUTOTUNE_PREFIX)},
        "failures": [n for n, _ in failures],
        "records": records,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {os.path.normpath(JSON_PATH)} "
          f"({len(records)} records)")
    if check:
        problems = check_regressions(payload["summary"], baseline_summary)
        for p in problems:
            print(f"# REGRESSION: {p}")
        if problems:
            raise SystemExit(1)
        gated = [k for k in CHECK_BANDS if k in payload["summary"]]
        print(f"# perf gate passed ({len(gated)} keys checked)")
    if failures:
        print(f"# FAILURES: {[n for n, _ in failures]}")
        raise SystemExit(1)
    print("# all benchmarks passed")


if __name__ == "__main__":
    main()
