"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the continuous-batching SpecOffload serving engine end-to-end on the
default JAX device, or emits the production sharding plan for the
selected arch on the v5e mesh (``--plan``).  ``--reduced`` (the default)
serves tiny widths that run on a CPU; ``--no-reduced`` serves the
configs as they are, e.g. the Mixtral-8x7B / Mistral-7B pair at
published widths on one TPU v5e::

    python -m repro.launch.serve --arch mixtral-8x7b-v5e-pair \
        --no-reduced --env v5e --batch 8 --n-cand 4 --length-bucket 256 \
        --prompt-len 256 --gen 32

Requests arrive on a Poisson trace (``--rate`` req/s, virtual clock);
the report covers slot occupancy, TTFT / end-to-end latency percentiles,
and sustained tokens/s.

``--async`` serves the same trace through the always-on asyncio front
door instead (:mod:`repro.serving.server`): real clock, two tenants
with weighted fairness + priority preemption, bounded admission queue,
token-by-token streaming, graceful drain.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import PAIRS, get_config
from repro.configs.base import MISTRAL_7B
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import (SchedulerConfig, ServingEngine,
                                  latency_percentiles)
from repro.serving.trace import poisson_requests
from repro.sim.hardware import ENVS


def _serve_async(eng, prompts, gens, args):
    """submit -> stream -> drain through the asyncio front door: an
    open-loop two-tenant Poisson replay with live token streaming."""
    import asyncio

    from repro.serving.engine import latency_percentiles
    from repro.serving.server import AsyncServingServer
    from repro.serving.trace import replay_open_loop, \
        tenant_poisson_requests

    reqs = tenant_poisson_requests(
        prompts, gens, args.rate,
        {"acme": {"share": 2.0, "priority": 1},
         "beta": {"share": 1.0, "priority": 0}})

    async def drive():
        async with AsyncServingServer(eng, max_queue=max(4,
                                                         args.batch * 4)
                                      ) as srv:
            tokens, handles = await replay_open_loop(srv, reqs,
                                                     speed=args.speed)
        return tokens, handles, srv.tenant_report()

    tokens, handles, per_tenant = asyncio.run(drive())
    st = eng.stats()
    toks = sum(len(v) for v in tokens.values() if v is not None)
    print(f"async-served {len(handles)} requests, {toks} streamed "
          f"tokens in {st['wall_s']:.1f}s engine wall "
          f"({eng.throughput(handles):.2f} tok/s on {device_line()}, "
          f"config '{eng.target_cfg.name}')")
    print(f"occupancy={st['mean_occupancy']:.2f} over {st['rounds']} "
          f"rounds, fused compiles={st['fused_compiles']}, "
          f"rejected={st['rejected']}, preempted={st['preempted']}, "
          f"drained={not eng.has_work()}")
    for t, d in per_tenant.items():
        print(f"  tenant {t}: {d['requests']} reqs  ttft "
              + "  ".join(f"{k}={v:.3f}s" for k, v in d['ttft_s'].items()))
    pct = latency_percentiles(handles, "latency_s")
    print("  e2e : " + "  ".join(f"{k}={v:.3f}s" for k, v in pct.items()))
    _report_request_obs(eng)


def _report_request_obs(eng):
    """Print the request-timeline summary, SLO compliance and any
    dumped postmortem bundles (when the respective knobs are on)."""
    from repro.obs import timelines_summary
    tls = eng.request_timelines()
    if tls:
        s = timelines_summary(tls)
        print(f"timelines: {s['requests']} reqs  "
              f"queue={s['queue_s_total']:.2f}s  "
              f"prefill={s['prefill_s_total']:.2f}s  "
              f"decode={s['decode_s_total']:.2f}s  "
              f"stall={s['stall_s_total']:.2f}s")
    rep = eng.slo_report()
    if rep is not None:
        for key, c in rep["compliance"].items():
            print(f"  slo {key}: {c['compliance']:.0%} of "
                  f"{c['evaluated']} in objective "
                  f"({c['violations']} violations)")
    if eng.recorder is not None and eng.recorder.bundles:
        for p in eng.recorder.bundles:
            print(f"  postmortem bundle: {p}")


def device_line() -> str:
    """``platform:device_kind xN`` of the devices JAX runs on."""
    devs = jax.devices()
    return f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}"


def model_pair(arch: str, reduced: bool):
    """(target, draft) configs for ``--arch``: a named pair from
    ``PAIRS``, or a target with the Mistral-7B draft."""
    tcfg, dcfg = PAIRS[arch] if arch in PAIRS else (get_config(arch),
                                                    MISTRAL_7B)
    if reduced:
        tcfg = tcfg.reduced(d_model=128)
        dcfg = dcfg.reduced(d_model=64, vocab=tcfg.vocab_size)
    return tcfg, dcfg


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b",
                    help="target config, or a target/draft pair: "
                         + ", ".join(sorted(PAIRS)))
    ap.add_argument("--env", default="env1", choices=sorted(ENVS))
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve tiny widths (CPU-feasible); --no-reduced "
                         "serves the configs as they are")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--n-cand", type=int, default=3)
    ap.add_argument("--batch", type=int, default=2,
                    help="slots per interleaved half-batch")
    ap.add_argument("--length-bucket", type=int, default=None,
                    help="left-pad prompts to a multiple of this many "
                         "tokens so prefill compiles once per bucket")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrival rate (req/s, virtual clock)")
    ap.add_argument("--admission", default="fifo", choices=("fifo", "sjf"))
    ap.add_argument("--async", dest="run_async", action="store_true",
                    help="serve through the always-on asyncio front "
                         "door (real clock, 2 tenants, bounded "
                         "admission queue, token streaming, drain)")
    ap.add_argument("--speed", type=float, default=8.0,
                    help="arrival-gap compression for --async")
    ap.add_argument("--plan", action="store_true",
                    help="print the ParaSpec plan + placement and exit")
    ap.add_argument("--timelines", action="store_true",
                    help="record per-request phase timelines "
                         "(queue/prefill/decode/stall) and print a "
                         "summary digest")
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="declare a TTFT SLO (seconds); compliance and "
                         "violations are reported at exit")
    ap.add_argument("--slo-e2e", type=float, default=None,
                    help="declare an end-to-end latency SLO (seconds)")
    ap.add_argument("--postmortem-dir", default=None,
                    help="dump flight-recorder postmortem bundles here "
                         "on SLO violations / anomalies")
    return ap


def build_engine(args) -> ServingEngine:
    """The serving engine ``args`` describe, with weights from seed 0."""
    tcfg, dcfg = model_pair(args.arch, args.reduced)
    if dcfg.vocab_size != tcfg.vocab_size:
        raise ValueError(f"draft vocab {dcfg.vocab_size} != target vocab "
                         f"{tcfg.vocab_size}; serve {args.arch!r} --reduced")
    slos = []
    if args.slo_ttft is not None:
        slos.append({"name": "ttft", "metric": "ttft_s",
                     "threshold_s": args.slo_ttft})
    if args.slo_e2e is not None:
        slos.append({"name": "e2e", "metric": "e2e_s",
                     "threshold_s": args.slo_e2e})
    eng = ServingEngine(tcfg, dcfg, ENVS[args.env],
                        config=SchedulerConfig(
                            max_batch=args.batch, n_cand=args.n_cand,
                            admission=args.admission,
                            length_bucket=args.length_bucket,
                            clock="real" if args.run_async else "virtual",
                            qos=args.run_async, preempt=args.run_async,
                            tenant_weights={"acme": 2.0, "beta": 1.0},
                            request_timeline=args.timelines,
                            slos=tuple(slos),
                            postmortem_dir=args.postmortem_dir))
    eng.init_from_seed(0)
    return eng


def main(argv=None):
    args = build_parser().parse_args(argv)
    hw = ENVS[args.env]

    if args.plan:
        from repro.core.placement import plan_placement
        from repro.core.planner import ParaSpecPlanner, Workload
        tcfg, dcfg = model_pair(args.arch, reduced=False)
        planner = ParaSpecPlanner(tcfg, dcfg, hw)
        rep = planner.search(Workload(args.prompt_len, args.gen))
        print(f"policy (bs_prefill, bs_decode, bs_draft, n_cand) = "
              f"{rep.policy.astuple()}")
        print(f"predicted throughput = {rep.throughput:.2f} tok/s on "
              f"{hw.name}")
        plan = plan_placement(tcfg, dcfg, hw)
        print(f"placement: hbm={plan.hbm_used/2**30:.1f}G "
              f"host={plan.host_used/2**30:.1f}G "
              f"disk={plan.disk_used/2**30:.1f}G")
        for n in plan.notes:
            print(" note:", n)
        return

    enable_compile_cache()
    eng = build_engine(args)
    tcfg = eng.target_cfg
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, tcfg.vocab_size,
                            args.prompt_len).astype(np.int32)
               for _ in range(args.requests)]
    gens = rng.integers(max(2, args.gen // 2), args.gen + 1, args.requests)

    if args.run_async:
        _serve_async(eng, prompts, gens.tolist(), args)
        return

    for r in poisson_requests(prompts, gens.tolist(), args.rate):
        eng.submit(r)

    done = eng.run()
    st = eng.stats()
    toks = sum(len(r.result) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in "
          f"{st['wall_s']:.1f}s wall ({eng.throughput(done):.2f} tok/s on "
          f"{device_line()}, config '{tcfg.name}')")
    print(f"occupancy={st['mean_occupancy']:.2f} over {st['rounds']} "
          f"rounds, fused compiles={st['fused_compiles']}")
    for name, attr in (("ttft", "ttft_s"), ("e2e", "latency_s")):
        pct = latency_percentiles(done, attr)
        print(f"{name:>5}: " + "  ".join(f"{k}={v:.3f}s"
                                         for k, v in pct.items()))
    _report_request_obs(eng)


if __name__ == "__main__":
    main()
