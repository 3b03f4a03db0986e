"""Serving launcher: bring up the batched engine on a model config and
drain a synthetic request stream, then print the latency/throughput report
(tok/s, p50/p95 per-request latency, recompile counts, §6 pJ/token).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --slots 4 --requests 16

``--engine legacy`` runs the seed host-driven engine on the same stream
(the A/B the serve benchmark automates).
"""
import argparse
import dataclasses
import json
import sys
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--quant", default="timefloats",
                    choices=["timefloats", "none"])
    ap.add_argument("--engine", default="fused",
                    choices=["fused", "legacy"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged cache pool + radix prefix cache (DESIGN §8;"
                         " attention/MLA archs)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared system-prompt tokens prepended to every "
                         "request (exercises the radix prefix cache)")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="chunked prefill: pow2 chunk size (DESIGN §10; "
                         "0 = whole-prompt waves; attention/MLA archs)")
    ap.add_argument("--sched", default="fcfs", choices=["fcfs", "cost"],
                    help="admission policy: arrival order or pJ-scored "
                         "cost-aware (hw twin Table-I costs)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding (DESIGN §12): ngram draft + "
                         "batched chain verify; greedy streams stay bitwise "
                         "identical to spec-off (fused engine, temp 0)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative chain depth (draft tokens per step)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome trace-event JSON of the "
                         "drain (DESIGN §11; load at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry snapshot (.json = "
                         "flat dict, else Prometheus text)")
    ap.add_argument("--tokens-out", default=None, metavar="PATH",
                    help="write each request's generated tokens as JSON "
                         "({uid: [tokens]})")
    ap.add_argument("--trace-capacity", type=int, default=1 << 16,
                    help="tracer ring size; overflow voids the trace's "
                         "energy certification")
    ap.add_argument("--health", action="store_true",
                    help="streaming drift detectors + SLO burn report "
                         "(DESIGN §13; fused engine)")
    ap.add_argument("--slo-ttft-p95", type=float, default=5.0,
                    help="p95 TTFT objective in seconds")
    ap.add_argument("--slo-itl-p95", type=float, default=1.0,
                    help="p95 ITL objective in seconds")
    ap.add_argument("--inject-lag", default=None, metavar="STEP:SECONDS",
                    help="sleep SECONDS before every engine step from step "
                         "STEP on — a synthetic latency regression the "
                         "drift detector must catch (the CI health smoke)")
    ap.add_argument("--expect-alert", action="store_true",
                    help="exit 1 unless at least one health alert fired")
    ap.add_argument("--wear-weight", type=float, default=0.0,
                    help="wear-aware admission (§10/§13): surcharge "
                         "request scores by weight x endurance_frac "
                         "(requires --sched cost, timefloats quant)")
    ap.add_argument("--wear-prior-steps", type=int, default=0,
                    help="pre-age the wear monitor by this many optimizer "
                         "steps before serving (a fleet mid-life chip)")
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_config, reduced_for_smoke
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import model as M
    from repro.obs.export import (validate_health, validate_trace,
                                  write_chrome_trace, write_metrics)
    from repro.obs.trace import Tracer
    from repro.serve.engine import Engine
    from repro.serve.legacy import LegacyEngine
    from repro.serve.request import Request, percentile as _pct
    from repro.serve.spec import SpecConfig

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_for_smoke(cfg)
    cfg = dataclasses.replace(cfg, quant=args.quant)
    print(f"arch={args.arch} reduced={args.reduced} engine={args.engine} "
          f"params={cfg.param_count() / 1e6:.1f}M slots={args.slots}")

    params = M.init(cfg, jax.random.PRNGKey(args.seed))
    if args.engine != "fused" and (args.paged or args.chunk_tokens
                                   or args.sched != "fcfs" or args.spec
                                   or args.health or args.wear_weight):
        print("--paged/--chunk-tokens/--sched/--spec/--health/--wear-weight"
              " require the fused engine", file=sys.stderr)
        return 2
    if args.spec and args.temperature > 0:
        print("--spec requires greedy decoding (temperature 0)",
              file=sys.stderr)
        return 2
    if args.wear_weight and (args.quant != "timefloats"
                             or args.sched != "cost"):
        print("--wear-weight needs the pJ-scored scheduler on the "
              "timefloats twin (--sched cost --quant timefloats)",
              file=sys.stderr)
        return 2
    tracer = Tracer(capacity=args.trace_capacity) if args.trace_out else None
    wear_endurance = None
    wear_monitor = None
    if args.wear_weight:
        # A live endurance source (DESIGN §13): the per-tile wear monitor,
        # optionally pre-aged — census-free (serving only needs the
        # placement's write books, and an empty census costs zeros).
        from repro.hw.mapper import map_params
        from repro.hw.schedule import HwMonitor

        wear_monitor = HwMonitor(map_params(params, cfg), events=[])
        if args.wear_prior_steps:
            wear_monitor.resume_at(args.wear_prior_steps)
        wear_endurance = lambda: wear_monitor.summary()["endurance_frac"]
    hm = None
    slos = ()
    if args.health:
        from repro.obs.health import HealthMonitor, default_serve_slos

        hm = HealthMonitor(tracer=tracer)
        slos = default_serve_slos(args.slo_ttft_p95, args.slo_itl_p95)
    if args.engine == "fused":
        eng = Engine(params, cfg, slots=args.slots, max_len=args.max_len,
                     seed=args.seed, paged=args.paged,
                     page_size=args.page_size,
                     chunk_tokens=args.chunk_tokens or None,
                     sched=args.sched, tracer=tracer,
                     spec=(SpecConfig(k=args.spec_k) if args.spec else None),
                     wear_weight=args.wear_weight,
                     wear_endurance=wear_endurance,
                     health=hm, slos=slos)
    else:
        eng = LegacyEngine(params, cfg, slots=args.slots,
                           max_len=args.max_len, seed=args.seed,
                           tracer=tracer)
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size,
                          size=args.prefix_len).astype(np.int32)
    motif = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    for uid in range(args.requests):
        plen = int(rng.integers(4, min(64, args.max_len // 2)))
        if args.spec:
            # Motif-tiled prompts: repetitive structure the ngram draft can
            # actually extend (random prompts would verify correctly but
            # accept almost nothing — a useless smoke).
            prompt = np.tile(motif, plen // len(motif) + 1)[:plen]
        else:
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=plen).astype(np.int32)
        if args.prefix_len:
            prompt = np.concatenate([shared, prompt])
        eng.submit(Request(uid=uid, prompt=prompt,
                           max_new_tokens=args.max_new,
                           temperature=args.temperature))
    t0 = time.time()
    if args.inject_lag:
        # Manual drive with a synthetic latency step: sleeping BETWEEN
        # engine steps inflates the inter-token latency (the ITL basis is
        # the previous step's token timestamp), which is exactly the
        # series the drift detector watches.
        lag_step, lag_s = args.inject_lag.split(":")
        lag_step, lag_s = int(lag_step), float(lag_s)
        done, n_steps = [], 0
        while (eng.active or eng._chunking or eng.queue) and n_steps < 10_000:
            if n_steps >= lag_step:
                time.sleep(lag_s)
            done.extend(eng.step())
            n_steps += 1
        assert n_steps < 10_000, "inject-lag drive never drained"
    else:
        done = eng.run_until_drained()
    dt = time.time() - t0
    new_tokens = sum(len(f.tokens) for f in done)
    print(f"served {len(done)}/{args.requests} requests, {new_tokens} tokens "
          f"in {dt:.1f}s ({new_tokens / max(dt, 1e-9):.1f} tok/s)")
    lats = [f.latency_s for f in done if f.latency_s > 0]
    traces = eng.compile_cache_stats()
    n_prefill = traces.get("prefill_total", traces.get("prefill", 0))
    n_decode = traces.get("decode_total",
                          traces.get("decode_and_sample",
                                     traces.get("decode", 0)))
    ttfts = [f.ttft_s for f in done if f.ttft_s > 0]
    print(f"latency p50 {_pct(lats, 50):.2f}s p95 {_pct(lats, 95):.2f}s | "
          f"ttft p50 {_pct(ttfts, 50):.2f}s p95 {_pct(ttfts, 95):.2f}s | "
          f"steps {getattr(eng, 'steps', 0)} | "
          f"compiles: prefill {n_prefill}, decode {n_decode} | "
          f"host transfers {getattr(eng, 'host_transfers', 'n/a')}")

    def _hp(name: str, p: float) -> float:
        h = eng.metrics.get(name)
        return h.percentile(p) if h is not None and h.count else 0.0

    # Histogram-backed percentiles from the always-on metrics registry
    # (log-bucket upper bounds, ≤ ~9% relative; DESIGN §11).
    print("metrics: ttft "
          + " ".join(f"p{p} {_hp('serve_ttft_s', p) * 1e3:.1f}ms"
                     for p in (50, 95, 99))
          + " | itl "
          + " ".join(f"p{p} {_hp('serve_itl_s', p) * 1e3:.2f}ms"
                     for p in (50, 95, 99)))
    if args.chunk_tokens:
        print(f"chunked: {getattr(eng, 'chunk_waves', 0)} chunk waves "
              f"(chunk_tokens={args.chunk_tokens}, sched={args.sched}), "
              f"{getattr(eng, 'decode_stall_steps', 0)} stalled steps")
    if args.spec:
        st = eng.stats()
        print(f"spec: k={int(st['spec_k'])} accept rate "
              f"{st['spec_accept_rate']:.1%} "
              f"({int(st['spec_accepted'])}/{int(st['spec_proposed'])} "
              f"drafts), {st['spec_tokens_per_step']:.2f} emitted "
              f"tokens/step")
        if st["spec_proposed"] <= 0:
            return 1
    hw = eng.hw_telemetry()
    if hw is not None:  # §6 twin: projected crossbar energy + utilization
        per_tok = [f.pj_per_token for f in done]
        p50 = f"{_pct(per_tok, 50):.0f}" if per_tok else "n/a"
        print(f"hw twin: {hw['total_pj'] / 1e6:.2f} uJ total "
              f"({hw['idle_pj'] / 1e6:.2f} uJ idle), slot utilization "
              f"{hw['slot_utilization']:.1%}, pJ/token p50 {p50}")
        if args.paged:
            print(f"prefix credit: {hw['prefix_saved_pj'] / 1e6:.2f} uJ "
                  f"saved over {int(hw['prefix_hits'])} hits "
                  f"({int(hw['prefix_tokens_saved'])} prefill positions)")
        if args.spec and hw.get("spec_accepted_tokens"):
            print(f"spec energy: {hw['spec_pj_per_accepted_token']:.0f} "
                  f"pJ/accepted-token "
                  f"({hw['spec_rejected_pj'] / 1e6:.2f} uJ on rejected "
                  f"positions)")
    health_doc = None
    if hm is not None:
        from repro.obs.health import export_slo_gauges

        rep = hm.report(slos=slos, metrics=eng.metrics)
        export_slo_gauges(eng.metrics, rep.slos)  # before write_metrics
        health_doc = rep.to_dict()
        print(f"health: {len(rep.alerts)} alerts over "
              f"{len(rep.series)} series "
              f"({', '.join(sorted(rep.series))})")
        for a in rep.alerts:
            print(f"  ALERT {a.series} {a.direction} at sample {a.sample}: "
                  f"value {a.value:.4g} vs baseline {a.baseline:.4g} "
                  f"(z={a.z:.1f}, {a.kind} score {a.score:.1f})")
        for st in rep.slos:
            print(f"  SLO {st.name}: {st.objective}({st.metric}) "
                  f"{st.observed:.4g} vs target {st.target:g} — "
                  f"burn rate {st.burn_rate:.2f}, "
                  f"budget {st.budget_remaining:+.2f}, "
                  f"{'OK' if st.ok else 'VIOLATED'}")
        if args.expect_alert and not rep.alerts:
            print("expected a health alert; none fired", file=sys.stderr)
            return 1
    if wear_monitor is not None:
        s = wear_monitor.summary()
        print(f"wear admission: weight {args.wear_weight:g}, endurance "
              f"frac {s['endurance_frac']:.3g} "
              f"({int(s['writes_per_tile'])} writes/tile pre-aged)")
        if args.metrics_out:
            wear_monitor.export_gauges(eng.metrics)
    if args.metrics_out:
        write_metrics(args.metrics_out, eng.metrics)
        print(f"metrics written to {args.metrics_out}")
    if args.tokens_out:
        with open(args.tokens_out, "w") as f:
            json.dump({str(r.uid): [int(t) for t in r.tokens] for r in done},
                      f)
    if args.trace_out:
        meta = {"hw": hw, "engine": args.engine, "arch": args.arch}
        if health_doc is not None:
            meta["health"] = health_doc
        payload = write_chrome_trace(args.trace_out, tracer, metadata=meta)
        require = (("engine.step", "prefill", "decode")
                   if args.engine == "legacy" else None)
        problems = (validate_trace(payload, require) if require
                    else validate_trace(payload))
        if health_doc is not None:
            problems += validate_health(payload)
        print(f"trace written to {args.trace_out} "
              f"({payload['metadata']['events']} events, "
              f"{payload['metadata']['dropped']} dropped)")
        for p in problems:
            print(f"trace INVALID: {p}", file=sys.stderr)
        if problems:
            return 1
    if args.paged:  # §8 smoke contract: reuse happened, pool conserved
        st = eng.stats()
        conserved = (st["pool_pages_in_use"] + st["pool_pages_free"]
                     == st["pool_pages_total"])
        print(f"paged: hit rate {st['radix_hit_rate']:.1%} "
              f"({int(st['radix_hits'])} hits), pool "
              f"{int(st['pool_pages_in_use'])} used + "
              f"{int(st['pool_pages_free'])} free / "
              f"{int(st['pool_pages_total'])} pages, "
              f"{int(st['radix_evictions'])} evictions, "
              f"conserved={conserved}")
        if not conserved:
            return 1
        if args.prefix_len and not st["radix_hit_rate"] > 0:
            return 1
    return 0 if len(done) == args.requests else 1


if __name__ == "__main__":
    sys.exit(main())
