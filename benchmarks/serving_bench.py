"""Continuous-batching serving benchmark: Poisson arrival trace through
the slot scheduler on the reduced CPU config.

Reports slot occupancy, TTFT / end-to-end latency percentiles, sustained
tokens/s, peak resident target-KV bytes, and the fused-step compile count
(must stay 1 across all retirements/admissions).  Row format matches
benchmarks/run.py: ``(name, value, derived)``.

    PYTHONPATH=src python -m benchmarks.serving_bench [--requests N]
    # open-loop asyncio serving (2 tenants, bounded admission queue,
    # priority preemption) vs closed-loop run() on the same trace
    #   -> BENCH_serving_async.json
    PYTHONPATH=src python -m benchmarks.serving_bench --async
    # paged-vs-contiguous A/B on the same trace -> BENCH_serving_paged.json
    PYTHONPATH=src python -m benchmarks.serving_bench --compare [--out F]
    # chain-vs-tree speculation A/B at equal candidate budget
    #   -> BENCH_serving_tree.json
    PYTHONPATH=src python -m benchmarks.serving_bench --compare-spec
    # observability run: Perfetto trace + metrics snapshot + digest
    #   -> BENCH_serving_obs.json
    PYTHONPATH=src python -m benchmarks.serving_bench \\
        --trace-out trace.json --metrics-out metrics.json
"""
from __future__ import annotations

import numpy as np


def run(rows: list, requests: int = 10, gen: int = 8, rate: float = 2.0,
        seed: int = 0, paged: bool = True, kv_quant_cold: bool = False,
        prefix: str = "serving", trace: bool = False, n_cand: int = 2,
        spec_tree: tuple | None = None, vocab: int | None = None,
        request_timeline: bool = False) -> dict:
    import dataclasses

    from repro.configs.base import MIXTRAL_8X7B, MISTRAL_7B
    from repro.serving.engine import (SchedulerConfig, ServingEngine,
                                      latency_percentiles)
    from repro.serving.trace import poisson_requests

    tcfg = MIXTRAL_8X7B.reduced(d_model=64, **({"vocab": vocab} if vocab
                                               else {}))
    dcfg = MISTRAL_7B.reduced(d_model=32, vocab=tcfg.vocab_size)
    if spec_tree is not None:
        # tree speculation needs an all-attention draft; swap the SWA
        # pattern for full attention at the same size
        dcfg = dataclasses.replace(dcfg, layer_pattern=("attn",) * 2,
                                   n_layers=2)
    # length_bucket pads admitted prompts to one shape so the trace
    # measures scheduler behavior, not per-length prefill compiles (the
    # benchmark doesn't assert raw-prompt losslessness)
    eng = ServingEngine(tcfg, dcfg,
                        config=SchedulerConfig(max_batch=2, n_cand=n_cand,
                                               spec_tree=spec_tree,
                                               length_bucket=16,
                                               paged=paged,
                                               kv_quant_cold=kv_quant_cold,
                                               trace=trace,
                                               request_timeline=
                                               request_timeline))
    eng.init_from_seed(seed)

    rng = np.random.default_rng(seed)
    # heavy-tailed prompt mix: mostly short chats plus occasional long
    # documents.  The contiguous layout must size every slot for the
    # tail; the paged pool only holds blocks each sequence actually uses.
    lens = [int(rng.integers(48, 81)) if rng.random() < 0.25
            else int(rng.integers(8, 17)) for _ in range(requests)]
    prompts = [rng.integers(0, tcfg.vocab_size, L).astype(np.int32)
               for L in lens]
    gens = rng.integers(max(2, gen // 2), gen + 1, requests)
    for r in poisson_requests(prompts, gens.tolist(), rate, seed):
        eng.submit(r)

    done = eng.run()
    st = eng.stats()
    ttft = latency_percentiles(done, "ttft_s")
    e2e = latency_percentiles(done, "latency_s")
    kv = st["kv"]
    rows.append((f"{prefix}/occupancy", st["mean_occupancy"], "measured"))
    rows.append((f"{prefix}/tok_per_s", eng.throughput(done), "measured"))
    rows.append((f"{prefix}/ttft_p50_s", ttft["p50"], "measured"))
    rows.append((f"{prefix}/ttft_p95_s", ttft["p95"], "measured"))
    rows.append((f"{prefix}/e2e_p50_s", e2e["p50"], "measured"))
    rows.append((f"{prefix}/e2e_p95_s", e2e["p95"], "measured"))
    rows.append((f"{prefix}/peak_kv_bytes", float(kv["peak_kv_bytes"]),
                 "measured"))
    rows.append((f"{prefix}/fused_compiles", float(st["fused_compiles"]),
                 "measured"))
    return {"done": done, "stats": st, "ttft": ttft, "e2e": e2e,
            "engine": eng}


def _summary(out: dict) -> dict:
    """JSON-friendly digest of one run() result."""
    st = out["stats"]
    kv = {k: v for k, v in st["kv"].items() if k != "allocators"}
    return {
        "requests": len(out["done"]),
        "rounds": st["rounds"],
        "occupancy": st["mean_occupancy"],
        "tok_per_s": st["tok_per_s"],
        "ttft_s": out["ttft"],
        "e2e_s": out["e2e"],
        "decode_s": {  # first token -> last token
            k: float(v) for k, v in zip(
                ("p50", "p95", "p99"),
                np.percentile([r.decode_s for r in out["done"]],
                              (50, 95, 99)))},
        "fused_compiles": st["fused_compiles"],
        "rejected": st["rejected"],
        "kv": kv,
        "peak_kv_bytes": float(kv["peak_kv_bytes"]),
    }


def compare(requests: int = 10, gen: int = 8, rate: float = 2.0,
            seed: int = 0) -> dict:
    """Contiguous vs paged vs paged+int8 on the *same* Poisson trace."""
    variants = {
        "contiguous": dict(paged=False),
        "paged": dict(paged=True),
        "paged_int8_cold": dict(paged=True, kv_quant_cold=True),
    }
    report: dict = {"trace": {"requests": requests, "gen": gen,
                              "rate_rps": rate, "seed": seed,
                              "config": "MIXTRAL_8X7B.reduced(d_model=64)"
                                        " / max_batch=2 x2, n_cand=2"}}
    for name, kw in variants.items():
        rows: list = []
        out = run(rows, requests, gen, rate, seed, prefix=name, **kw)
        report[name] = _summary(out)
    base, pag = report["contiguous"], report["paged"]
    report["verdict"] = {
        "peak_kv_reduction": 1.0 - pag["peak_kv_bytes"]
        / base["peak_kv_bytes"],
        "tok_per_s_ratio": pag["tok_per_s"] / base["tok_per_s"],
        "int8_peak_kv_reduction": 1.0
        - report["paged_int8_cold"]["peak_kv_bytes"]
        / base["peak_kv_bytes"],
    }
    return report


def _accept_per_pass(eng, mode: str) -> dict:
    """Accepted-candidates-per-target-pass from the acceptance counters:
    emitted tokens per verify pass = accepted/rounds + 1 (the bonus)."""
    snap = eng.metrics()["metrics"]["counters"]
    lab = f'{{mode="{mode}"}}'
    acc = snap.get("spec_tokens_accepted_total", {}).get(lab, 0.0)
    waste = snap.get("spec_tokens_wasted_total", {}).get(lab, 0.0)
    rounds = snap.get("spec_verify_rounds_total", {}).get(lab, 0.0)
    return {"accepted_total": acc, "wasted_total": waste,
            "verify_rounds": rounds,
            "accepted_per_pass": acc / max(rounds, 1.0),
            "emitted_per_pass": acc / max(rounds, 1.0) + 1.0,
            "waste_frac": waste / max(acc + waste, 1.0)}


def compare_spec(requests: int = 10, gen: int = 8, rate: float = 2.0,
                 seed: int = 0, tree: tuple = (3, 2),
                 vocab: int = 13) -> dict:
    """Chain vs tree speculation on the *same* Poisson trace at equal
    candidate budget (chain n_cand = tree nodes - 1).

    A small vocab makes the tiny random draft/target pair agree often
    enough that acceptance behavior is measurable; the tree's extra
    siblings then raise the chance *some* path survives each depth, which
    is exactly the accepted-tokens-per-target-pass gain the planner's
    tree model predicts at low acceptance rates.
    """
    from repro.core.spec_decode import tree_n_nodes

    budget = tree_n_nodes(tree) - 1         # candidates per verify pass
    report: dict = {"trace": {"requests": requests, "gen": gen,
                              "rate_rps": rate, "seed": seed,
                              "tree": list(tree),
                              "candidate_budget": budget,
                              "vocab": vocab,
                              "config": "MIXTRAL_8X7B.reduced(d_model=64)"
                                        " / max_batch=2 x2"}}
    for name, kw in (("chain", dict(n_cand=budget)),
                     ("tree", dict(spec_tree=tuple(tree)))):
        rows: list = []
        out = run(rows, requests, gen, rate, seed, prefix=f"spec_{name}",
                  vocab=vocab, **kw)
        s = _summary(out)
        s["acceptance"] = _accept_per_pass(out["engine"], name)
        report[name] = s
    ch = report["chain"]["acceptance"]
    tr = report["tree"]["acceptance"]
    report["verdict"] = {
        "chain_accepted_per_pass": ch["accepted_per_pass"],
        "tree_accepted_per_pass": tr["accepted_per_pass"],
        "accepted_per_pass_ratio": tr["accepted_per_pass"]
        / max(ch["accepted_per_pass"], 1e-9),
        "tok_per_s_ratio": report["tree"]["tok_per_s"]
        / max(report["chain"]["tok_per_s"], 1e-9),
        "waste_frac_chain": ch["waste_frac"],
        "waste_frac_tree": tr["waste_frac"],
    }
    return report


def _async_engine(clock: str, spec=None):
    """Reduced engine with the QoS knobs both async-A/B legs share."""
    from repro.configs.base import MIXTRAL_8X7B, MISTRAL_7B
    from repro.serving.engine import SchedulerConfig, ServingEngine

    tcfg = MIXTRAL_8X7B.reduced(d_model=64)
    dcfg = MISTRAL_7B.reduced(d_model=32, vocab=tcfg.vocab_size)
    # explicit max_len: the open-loop leg sizes caches at the *first*
    # arrival, so capacity must already cover the trace's longest
    # prompt (the closed-loop leg sees the whole queue up front)
    eng = ServingEngine(tcfg, dcfg, config=SchedulerConfig(
        max_batch=2, n_cand=2, length_bucket=16, max_len=160,
        clock=clock, qos=True,
        tenant_weights={"acme": 2.0, "beta": 1.0},
        preempt=True, preempt_min_remaining=2))
    return eng, tcfg


TENANTS = {"acme": {"share": 2.0, "priority": 1},
           "beta": {"share": 1.0, "priority": 0}}


def _tenant_trace(requests: int, gen: int, rate: float, seed: int,
                  vocab: int) -> list:
    from repro.serving.trace import tenant_poisson_requests

    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(48, 81)) if rng.random() < 0.25
            else int(rng.integers(8, 17)) for _ in range(requests)]
    prompts = [rng.integers(0, vocab, L).astype(np.int32) for L in lens]
    gens = rng.integers(max(2, gen // 2), gen + 1, requests)
    return tenant_poisson_requests(prompts, gens.tolist(), rate,
                                   TENANTS, seed)


def _tenant_ttft(handles: list) -> dict:
    from repro.serving.engine import latency_percentiles

    out: dict = {}
    for t in sorted({r.tenant for r in handles}):
        rs = [r for r in handles if r.tenant == t]
        out[t] = {"requests": len(rs),
                  "ttft_s": latency_percentiles(rs, "ttft_s"),
                  "e2e_s": latency_percentiles(rs, "latency_s")}
    return out


def async_compare(requests: int = 10, gen: int = 8, rate: float = 2.0,
                  seed: int = 0, speed: float = 8.0,
                  max_queue: int = 6) -> dict:
    """Open-loop asyncio leg vs the closed-loop ``run()`` path on the
    same two-tenant Poisson trace -> ``BENCH_serving_async.json``.

    The async leg streams token-by-token through
    :class:`repro.serving.server.AsyncServingServer` with a bounded
    admission queue (backpressure), weighted tenant fairness and
    priority preemption; ``speed`` compresses the arrival gaps so the
    CPU-reduced decode — not the trace clock — is the bottleneck.
    Streams must match the closed-loop results token for token
    (per-sequence losslessness), and the digest records per-tenant TTFT
    percentiles plus the throughput ratio between the legs.
    """
    import asyncio

    from repro.serving.server import AsyncServingServer
    from repro.serving.trace import replay_open_loop

    # ---- closed-loop leg: virtual clock, same trace -----------------
    eng, tcfg = _async_engine("virtual")
    eng.init_from_seed(seed)
    closed_reqs = _tenant_trace(requests, gen, rate, seed,
                                tcfg.vocab_size)
    for r in closed_reqs:
        eng.submit(r)
    closed_done = eng.run()
    closed_tps = eng.throughput(closed_done)
    closed_stats = eng.stats()

    # ---- open-loop async leg: real clock, same trace ----------------
    aeng, _ = _async_engine("real")
    aeng.init_from_seed(seed)
    trace = _tenant_trace(requests, gen, rate, seed, tcfg.vocab_size)

    async def _drive():
        async with AsyncServingServer(aeng, max_queue=max_queue) as srv:
            return await replay_open_loop(srv, trace, speed=speed)

    tokens, handles = asyncio.run(_drive())
    async_stats = aeng.stats()
    async_tps = aeng.throughput(handles)

    closed_by_rid = {r.rid: list(map(int, r.result)) for r in closed_done}
    parity = all(tokens.get(rid) == toks
                 for rid, toks in closed_by_rid.items())
    report = {
        "trace": {"requests": requests, "gen": gen, "rate_rps": rate,
                  "seed": seed, "speed": speed, "max_queue": max_queue,
                  "tenants": TENANTS,
                  "config": "MIXTRAL_8X7B.reduced(d_model=64) / "
                            "max_batch=2 x2, n_cand=2, qos+preempt"},
        "closed_loop": {"tok_per_s": closed_tps,
                        "rounds": closed_stats["rounds"],
                        "occupancy": closed_stats["mean_occupancy"],
                        "fused_compiles": closed_stats["fused_compiles"],
                        "per_tenant": _tenant_ttft(closed_done)},
        "async_open_loop": {"tok_per_s": async_tps,
                            "rounds": async_stats["rounds"],
                            "occupancy": async_stats["mean_occupancy"],
                            "fused_compiles":
                                async_stats["fused_compiles"],
                            "rejected": async_stats["rejected"],
                            "preempted": async_stats["preempted"],
                            "streamed": sum(1 for v in tokens.values()
                                            if v is not None),
                            "drained": not aeng.has_work(),
                            "per_tenant": _tenant_ttft(handles)},
        "verdict": {"stream_parity_with_closed_loop": parity,
                    "tok_per_s_ratio_async_over_closed":
                        async_tps / max(closed_tps, 1e-9)},
    }
    return report


def obs_run(requests: int = 10, gen: int = 8, rate: float = 2.0,
            seed: int = 0, trace_out: str | None = None,
            metrics_out: str | None = None) -> dict:
    """Observability benchmark: the same Poisson trace twice — once with
    the span tracer on (Perfetto trace, metrics snapshot) and once with
    tracing disabled (throughput parity + fused-compile baseline).
    Returns the ``BENCH_serving_obs.json`` digest; writes the raw
    trace/metrics JSON when paths are given.
    """
    import json

    from repro.obs import timelines_summary

    rows: list = []
    traced = run(rows, requests, gen, rate, seed, prefix="obs",
                 trace=True, request_timeline=True)
    eng = traced["engine"]
    rep = eng.metrics()
    if trace_out:
        with open(trace_out, "w") as f:
            json.dump(eng.chrome_trace(), f)
    if metrics_out:
        with open(metrics_out, "w") as f:
            json.dump(rep, f, indent=2)

    # parity leg: tracing off must keep the fused step at one compile and
    # throughput within noise of the paged baseline
    rows2: list = []
    plain = run(rows2, requests, gen, rate, seed, prefix="plain")
    snap = rep["metrics"]
    digest = {
        "trace": {"requests": requests, "gen": gen, "rate_rps": rate,
                  "seed": seed,
                  "config": "MIXTRAL_8X7B.reduced(d_model=64) / "
                            "max_batch=2 x2, n_cand=2"},
        "transfers": {
            "bytes_by_tier": snap["counters"].get(
                "transfer_bytes_total", {}),
            "seconds_by_tier": snap["counters"].get(
                "transfer_seconds_total", {}),
        },
        "acceptance_hist": snap["histograms"].get(
            "spec_accepted_tokens", {}),
        "kv_gauges": {k: v for k, v in snap["gauges"].items()
                      if k.startswith("kv_")},
        "pipeline_traces": snap["counters"].get(
            "pipeline_traces_total", {}),
        "traced_tok_per_s": traced["stats"]["tok_per_s"],
        "untraced_tok_per_s": plain["stats"]["tok_per_s"],
        "untraced_fused_compiles": plain["stats"]["fused_compiles"],
        "trace_events": len(eng.chrome_trace()["traceEvents"]),
        # request-level latency percentiles + per-request timeline
        # aggregate (the bench_compare regression gate keys on these)
        "ttft": traced["ttft"],
        "e2e": traced["e2e"],
        "request_timelines": timelines_summary(eng.request_timelines()),
    }
    return digest


def main():
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--async", dest="run_async", action="store_true",
                    help="open-loop asyncio serving leg (2 tenants, "
                         "bounded queue, preemption) vs the closed-loop "
                         "run() path on the same trace")
    ap.add_argument("--speed", type=float, default=8.0,
                    help="arrival-gap compression for the async leg")
    ap.add_argument("--async-out", default="BENCH_serving_async.json",
                    help="JSON report path for --async")
    ap.add_argument("--compare", action="store_true",
                    help="contiguous vs paged A/B on one fixed trace")
    ap.add_argument("--out", default="BENCH_serving_paged.json",
                    help="JSON report path for --compare")
    ap.add_argument("--compare-spec", action="store_true",
                    help="chain vs tree speculation A/B on one fixed "
                         "trace at equal candidate budget")
    ap.add_argument("--spec-tree", default="3,2",
                    help="tree branching per depth for --compare-spec")
    ap.add_argument("--spec-out", default="BENCH_serving_tree.json",
                    help="JSON report path for --compare-spec")
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto-loadable Chrome trace JSON "
                         "(enables the observability run)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics snapshot JSON (enables the "
                         "observability run)")
    ap.add_argument("--obs-out", default="BENCH_serving_obs.json",
                    help="digest path for the obs run")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.run_async:
        report = async_compare(args.requests, args.gen, args.rate,
                               speed=args.speed)
        with open(args.async_out, "w") as f:
            json.dump(report, f, indent=2)
        v = report["verdict"]
        a = report["async_open_loop"]
        print(f"wrote {args.async_out}")
        print(f"stream parity with closed loop: "
              f"{v['stream_parity_with_closed_loop']}; drained: "
              f"{a['drained']}; rejected {a['rejected']}, "
              f"preempted {a['preempted']}")
        print(f"tok/s async/closed: "
              f"{v['tok_per_s_ratio_async_over_closed']:.2f}x "
              f"({a['tok_per_s']:.2f} vs "
              f"{report['closed_loop']['tok_per_s']:.2f})")
        for t, d in a["per_tenant"].items():
            print(f"  tenant {t}: {d['requests']} reqs, ttft p50 "
                  f"{d['ttft_s']['p50']:.3f}s p95 "
                  f"{d['ttft_s']['p95']:.3f}s")
        return
    if args.trace_out or args.metrics_out:
        digest = obs_run(args.requests, args.gen, args.rate,
                         trace_out=args.trace_out,
                         metrics_out=args.metrics_out)
        with open(args.obs_out, "w") as f:
            json.dump(digest, f, indent=2)
        print(f"wrote {args.obs_out}"
              + (f", {args.trace_out}" if args.trace_out else "")
              + (f", {args.metrics_out}" if args.metrics_out else ""))
        print(f"tok/s traced {digest['traced_tok_per_s']:.2f} vs "
              f"untraced {digest['untraced_tok_per_s']:.2f}; "
              f"fused compiles (untraced) "
              f"{digest['untraced_fused_compiles']}")
        return
    if args.compare_spec:
        tree = tuple(int(k) for k in args.spec_tree.split(","))
        report = compare_spec(args.requests, args.gen, args.rate,
                              tree=tree)
        with open(args.spec_out, "w") as f:
            json.dump(report, f, indent=2)
        v = report["verdict"]
        print(f"wrote {args.spec_out}")
        print(f"accepted candidates per target pass: "
              f"chain {v['chain_accepted_per_pass']:.3f} vs "
              f"tree {v['tree_accepted_per_pass']:.3f} "
              f"({v['accepted_per_pass_ratio']:.2f}x)")
        print(f"tokens/s ratio (tree/chain): {v['tok_per_s_ratio']:.2f}x")
        return
    if args.compare:
        report = compare(args.requests, args.gen, args.rate)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        v = report["verdict"]
        print(f"wrote {args.out}")
        print(f"peak KV reduction (paged):      "
              f"{100 * v['peak_kv_reduction']:.1f}%")
        print(f"peak KV reduction (paged+int8): "
              f"{100 * v['int8_peak_kv_reduction']:.1f}%")
        print(f"tokens/s ratio (paged/contig):  "
              f"{v['tok_per_s_ratio']:.2f}x")
        return
    rows: list = []
    out = run(rows, args.requests, args.gen, args.rate)
    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")
    st = out["stats"]
    print(f"\n{len(out['done'])} requests, {st['rounds']} rounds, "
          f"occupancy {st['mean_occupancy']:.2f}, "
          f"{st['fused_compiles']} fused compile(s)")


if __name__ == "__main__":
    main()
