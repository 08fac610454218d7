"""Request-scoped observability: per-request timelines + Chrome tracks,
SLO monitor, anomaly-triggered flight recorder, and the bench_compare
regression gate.

The load-bearing guarantees:

* tracking is host-side only — traced and untraced runs stay
  token-identical with exactly one fused compile;
* per-request decode spans land inside the engine's round spans (the
  request view and the engine's round view describe one pipeline);
* a tight TTFT SLO on a two-tenant open-loop trace dumps exactly ONE
  schema-valid postmortem bundle (cooldown collapses the storm);
* bench_compare passes on the committed baseline and fails on a
  synthetically regressed digest.
"""
import asyncio
import json
import os

import numpy as np
import pytest

from repro.obs import NULL_REQUEST_TRACKER, SLO, FlightRecorder
from repro.obs.request_trace import (RequestTracker, inter_token_gaps,
                                     percentile_of, timelines_summary)
from repro.obs.schema import (validate_postmortem_bundle,
                              validate_request_timeline)
from repro.obs.slo import SLOMonitor, as_slos
from repro.serving.engine import SchedulerConfig, ServeRequest, ServingEngine

from conftest import tiny_config, tiny_draft_config


def _requests(n, seed=0, gen=(3, 8)):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        p = rng.integers(0, 61, int(rng.integers(5, 13))).astype(np.int32)
        out.append(ServeRequest(i, p,
                                max_new_tokens=int(rng.integers(*gen)),
                                tenant="acme" if i % 2 else "beta"))
    return out


def _engine(**cfg_kw):
    se = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                       config=SchedulerConfig(max_batch=2, n_cand=2,
                                              **cfg_kw))
    se.init_from_seed(0)
    return se


@pytest.fixture(scope="module")
def tracked():
    """One run with request timelines + span tracer, shared below."""
    se = _engine(request_timeline=True, trace=True)
    for r in _requests(5):
        se.submit(r)
    done = se.run()
    return se, done


# ---------------------------------------------------------------------------
# timelines: schema, phase accounting, per-request Chrome tracks


def test_timelines_validate_and_cover_every_request(tracked):
    se, done = tracked
    tls = se.request_timelines()
    assert len(tls) == len(done) == 5
    for tl in tls:
        assert validate_request_timeline(tl) == []
    by_rid = {tl["rid"]: tl for tl in tls}
    for r in done:
        tl = by_rid[r.rid]
        assert tl["tokens"] == len(r.result)
        assert tl["tenant"] == r.tenant
        assert tl["rejected"] is None
        # verify rounds alone can't exceed total decode attribution
        assert (sum(p["dur_s"] for p in tl["per_round"])
                <= tl["decode_s"] + 1e-9)
        assert tl["queue_s"] >= 0 and tl["stall_s"] >= 0
        p99 = tl["inter_token_p99_s"]
        assert p99 is None or p99 >= 0.0


def test_per_request_tracks_in_chrome_trace(tracked):
    se, done = tracked
    trace = se.chrome_trace()
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    for r in done:
        assert f"req:{r.rid}" in names, f"missing req:{r.rid} track"
    # every request shows queue, prefill and at least one decode span
    tids = {e["args"]["name"]: e["tid"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "thread_name"}
    for r in done:
        spans = [e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X" and e["tid"] == tids[f"req:{r.rid}"]]
        assert "queue" in spans and "prefill" in spans
        assert "verify" in spans


def test_request_decode_spans_inside_round_spans(tracked):
    """The request view and the round view describe one pipeline:
    each per-request verify span must lie inside some round span."""
    se, _ = tracked
    evs = se.chrome_trace()["traceEvents"]
    tids = {e["tid"]: e["args"]["name"] for e in evs
            if e.get("ph") == "M" and e["name"] == "thread_name"}
    rounds = [(e["ts"], e["ts"] + e["dur"]) for e in evs
              if e.get("ph") == "X" and tids[e["tid"]] == "round"
              and e["name"] == "round"]
    verify = [(e["ts"], e["ts"] + e["dur"]) for e in evs
              if e.get("ph") == "X" and e.get("cat") == "request"
              and e["name"] == "verify"]
    assert rounds and verify
    tol = 1e3   # us
    for v0, v1 in verify:
        assert any(r0 - tol <= v0 and v1 <= r1 + tol
                   for r0, r1 in rounds), "verify span outside all rounds"


def test_timelines_summary_aggregates(tracked):
    se, done = tracked
    s = timelines_summary(se.request_timelines())
    assert s["requests"] == len(done)
    assert s["tokens"] == sum(len(r.result) for r in done)
    assert s["decode_s_total"] > 0.0


# ---------------------------------------------------------------------------
# parity: tracking must never perturb the engine


def test_token_parity_and_one_compile_traced_vs_untraced(tracked):
    se, done = tracked
    assert se.stats()["fused_compiles"] == 1
    plain = _engine()                     # metrics only, no tracking
    assert plain.requests is NULL_REQUEST_TRACKER
    for r in _requests(5):
        plain.submit(r)
    plain_done = plain.run()
    assert plain.stats()["fused_compiles"] == 1
    assert plain.request_timelines() == []
    traced_by_rid = {r.rid: list(map(int, r.result)) for r in done}
    for r in plain_done:
        assert list(map(int, r.result)) == traced_by_rid[r.rid]


# ---------------------------------------------------------------------------
# SLOs: scoping, monitor, violation -> exactly one postmortem bundle


def test_slo_scoping_and_normalization():
    slo = SLO("gold_ttft", "ttft_s", 0.5, tenant="acme", priority=0)
    assert slo.applies("acme", 0) and not slo.applies("acme", 1)
    assert not slo.applies("beta", 0)
    every = SLO("any", "e2e_s", 1.0)
    assert every.applies("x", 9)
    norm = as_slos([{"name": "n", "metric": "queue_s",
                     "threshold_s": 2.0}, every])
    assert norm[0].metric == "queue_s" and norm[1] is every
    with pytest.raises(ValueError):
        SLO("bad", "nope_s", 1.0)


def test_slo_monitor_compliance_counts():
    mon = SLOMonitor([SLO("ttft", "ttft_s", 0.5)])
    good = ServeRequest(0, np.zeros(1, np.int32), arrival_s=0.0)
    good.first_token_s = 0.2
    bad = ServeRequest(1, np.zeros(1, np.int32), arrival_s=0.0)
    bad.first_token_s = 3.0
    mon.observe_ttft(good)
    mon.observe_ttft(bad)
    rep = mon.report()
    assert rep["violations"] == 1
    c = rep["compliance"]["ttft/default"]
    assert c["evaluated"] == 2 and c["compliance"] == 0.5
    assert mon.violations[0]["rid"] == 1


def test_tight_ttft_slo_dumps_exactly_one_valid_bundle(tmp_path):
    """Two-tenant open-loop trace through the asyncio front door with an
    unmeetable TTFT objective: every request violates, the cooldown
    collapses the storm into exactly one schema-valid bundle."""
    from repro.serving.server import AsyncServingServer

    out_dir = os.environ.get("REPRO_POSTMORTEM_DIR") or str(tmp_path)
    se = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                       config=SchedulerConfig(
                           max_batch=2, n_cand=2, clock="real", qos=True,
                           max_len=64, request_timeline=True,
                           slos=({"name": "tight_ttft",
                                  "metric": "ttft_s",
                                  "threshold_s": 1e-9},),
                           postmortem_dir=out_dir))
    se.init_from_seed(0)
    rng = np.random.default_rng(1)

    async def drive():
        async with AsyncServingServer(se, max_queue=8) as srv:
            handles = []
            for i in range(4):
                p = rng.integers(0, 61, 6).astype(np.int32)
                handles.append(await srv.submit(
                    p, max_new_tokens=4,
                    tenant="acme" if i % 2 else "beta"))
            return [await srv.collect(h) for h in handles]

    streams = asyncio.run(drive())
    assert all(len(s) > 0 for s in streams)
    rep = se.slo_report()
    assert rep["violations"] == 4                  # every request missed
    assert {k.split("/")[1] for k in rep["compliance"]} == {"acme", "beta"}
    bundles = [p for p in se.recorder.bundles
               if os.path.basename(p).endswith("slo_tight_ttft")]
    assert len(se.recorder.bundles) == len(bundles) == 1
    assert validate_postmortem_bundle(bundles[0]) == []
    with open(os.path.join(bundles[0], "manifest.json")) as f:
        man = json.load(f)
    assert man["reason"] == "slo_tight_ttft"
    with open(os.path.join(bundles[0], "config.json")) as f:
        cfg = json.load(f)
    assert cfg["slos"][0]["name"] == "tight_ttft"
    # stream deliveries landed on the timelines
    tls = se.request_timelines()
    assert sum(tl["deliveries"] for tl in tls) == sum(
        len(s) for s in streams)


def test_bundle_tampering_detected(tmp_path):
    rec = FlightRecorder(capacity=8, out_dir=str(tmp_path),
                         cooldown_s=0.0)
    rec.record_round({"round": 0, "t0": 1.0, "t1": 1.5})
    rec.record_instant("spike", {"depth": 9})
    path = rec.trigger("unit", {}, metrics={}, engine={
        "rounds": 1, "tokens_out": 0, "queue_depth": 9}, config={})
    assert path is not None and validate_postmortem_bundle(path) == []
    man_p = os.path.join(path, "manifest.json")
    with open(man_p) as f:
        man = json.load(f)
    man["schema"] = "bogus/v0"
    with open(man_p, "w") as f:
        json.dump(man, f)
    assert any("schema" in p for p in validate_postmortem_bundle(path))
    os.remove(os.path.join(path, "engine.json"))
    assert any("engine.json" in p
               for p in validate_postmortem_bundle(path))


# ---------------------------------------------------------------------------
# flight recorder: anomaly detectors, cooldown, bundle cap


def test_recorder_accept_collapse_and_queue_spike():
    rec = FlightRecorder(warmup=4)
    for _ in range(10):
        assert rec.check(accept_mean=0.8, queue_depth=1) is None
    hit = rec.check(accept_mean=0.05, queue_depth=1)
    assert hit is not None and hit[0] == "accept_collapse"
    rec2 = FlightRecorder(warmup=4)
    for _ in range(10):
        assert rec2.check(busy_frac=0.9, queue_depth=2) is None
    hit = rec2.check(busy_frac=0.9, queue_depth=40)
    assert hit is not None and hit[0] == "queue_spike"
    hit = rec2.check(busy_frac=0.1, queue_depth=2)
    assert hit is not None and hit[0] == "busy_drop"


def test_recorder_warmup_suppresses_detectors():
    rec = FlightRecorder(warmup=50)
    for _ in range(10):
        rec.check(accept_mean=0.8)
    assert rec.check(accept_mean=0.01) is None   # still warming up


def test_recorder_cooldown_and_cap(tmp_path):
    rec = FlightRecorder(out_dir=str(tmp_path), cooldown_s=3600.0)
    assert rec.trigger("a", metrics={}, engine={}, config={}) is not None
    assert rec.trigger("b", metrics={}, engine={}, config={}) is None
    assert len(rec.triggers) == 2 and len(rec.bundles) == 1
    capped = FlightRecorder(out_dir=str(tmp_path / "cap"),
                            cooldown_s=0.0, max_bundles=2)
    dumped = [capped.trigger(f"r{i}", metrics={}, engine={}, config={})
              for i in range(5)]
    assert sum(1 for p in dumped if p) == 2


def test_recorder_no_dir_never_touches_disk():
    rec = FlightRecorder(out_dir=None, cooldown_s=0.0)
    sentinel = []
    assert rec.trigger("x", metrics=lambda: sentinel.append(1)) is None
    assert rec.triggers and rec.bundles == [] and sentinel == []


# ---------------------------------------------------------------------------
# tracker units: inter-token cadence, delivery counting, disabled mode


def test_inter_token_gaps_and_percentile():
    rounds = [{"emitted": 2, "t1": 1.0}, {"emitted": 0, "t1": 1.5},
              {"emitted": 1, "t1": 2.0}, {"emitted": 3, "t1": 2.1}]
    gaps = inter_token_gaps(rounds)
    # r0: 2 tokens -> one zero gap; r2 first token 1.0s after r0; r3
    # first token 0.1s later plus two zero gaps
    assert gaps == [0.0, 1.0, pytest.approx(0.1), 0.0, 0.0]
    assert percentile_of(gaps, 99) == pytest.approx(1.0)
    assert percentile_of([5.0], 50) == 5.0
    assert np.isnan(percentile_of([], 50))


def test_tracker_preemption_accounting():
    tr = RequestTracker()
    req = ServeRequest(7, np.zeros(3, np.int32), max_new_tokens=8,
                       tenant="t")
    tr.on_submit(req, wall=0.0)
    tr.on_admit(req, 1.0, 1.25)              # queued 1s, prefill .25s
    req.first_token_s = 0.0                  # first token produced
    tr.on_round(req, 0, 1.3, 1.6, accepted=1, emitted=2)
    tr.on_preempt(req, wall=2.0)
    tr.on_admit(req, 3.0, 3.5, resumed=True)  # parked 1s, prefill .5s
    tr.on_round(req, 5, 3.6, 3.9, accepted=0, emitted=1, role="verify")
    tr.on_round(req, 6, 4.0, 4.2, role="draft")
    req.result = np.zeros(3, np.int32)
    tr.on_finish(req, wall=4.5)
    tl = tr.timeline(7)
    assert validate_request_timeline(tl) == []
    assert tl["queue_s"] == pytest.approx(1.0)
    assert tl["preempted_s"] == pytest.approx(1.0)
    assert tl["preemptions"] == 1
    assert tl["prefill_s"] == pytest.approx(0.75)
    assert tl["decode_s"] == pytest.approx(0.8)   # .3 + .3 + .2 (draft)
    assert tl["verify_rounds"] == 2
    assert tl["accepted_total"] == 1
    # stall = (4.5 - 1.0) - prefill - decode - preempted
    assert tl["stall_s"] == pytest.approx(3.5 - 0.75 - 0.8 - 1.0)


def test_null_tracker_is_shared_noop():
    assert NULL_REQUEST_TRACKER.enabled is False
    assert NULL_REQUEST_TRACKER.timelines() == []
    assert NULL_REQUEST_TRACKER.timeline(0) is None
    NULL_REQUEST_TRACKER.on_round(None, 0, 0.0, 1.0)   # never raises


# ---------------------------------------------------------------------------
# bench_compare: the regression gate itself


def _baseline_digest():
    return {
        "untraced_tok_per_s": 10.0, "traced_tok_per_s": 5.0,
        "untraced_fused_compiles": 1,
        "ttft": {"p50": 1.0, "p95": 2.0},
    }


def test_bench_compare_passes_on_identical_digest():
    from benchmarks.bench_compare import compare_digests
    base = _baseline_digest()
    rep = compare_digests(base, json.loads(json.dumps(base)))
    assert rep["ok"] and all(c["ok"] for c in rep["checks"])


def test_bench_compare_fails_on_synthetic_regression():
    from benchmarks.bench_compare import compare_digests
    base = _baseline_digest()
    regressed = json.loads(json.dumps(base))
    regressed["untraced_tok_per_s"] = 1.0          # collapsed throughput
    regressed["ttft"]["p95"] = 60.0                # latency blow-up
    regressed["untraced_fused_compiles"] = 2       # shape leak
    rep = compare_digests(base, regressed)
    assert not rep["ok"]
    failed = {c["name"] for c in rep["checks"] if not c["ok"]}
    assert {"untraced_tok_per_s", "ttft_p95_s",
            "fused_compiles"} <= failed
    # a metric missing from the baseline is skipped, not failed
    del base["ttft"]
    rep2 = compare_digests(base, regressed)
    skipped = {c["name"]: c for c in rep2["checks"]}
    assert skipped["ttft_p95_s"]["ok"]
    assert "skipped" in skipped["ttft_p95_s"]["note"]


def test_bench_compare_tolerances_applied():
    from benchmarks.bench_compare import compare_digests
    base = _baseline_digest()
    mild = json.loads(json.dumps(base))
    mild["untraced_tok_per_s"] = 6.0    # 0.6x: inside the 0.35 floor
    mild["ttft"]["p50"] = 2.5           # 2.5x: inside the 3x ceiling
    assert compare_digests(base, mild)["ok"]
    assert not compare_digests(base, mild,
                               {"tol_throughput": 0.9})["ok"]


def test_committed_baseline_has_gate_metrics():
    """The committed BENCH_serving_obs.json must expose every metric the
    CI gate keys on (else the gate silently skips them)."""
    from benchmarks.bench_compare import CHECKS, _lookup
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_serving_obs.json")
    with open(path) as f:
        base = json.load(f)
    for name, keys, _, _ in CHECKS:
        v = _lookup(base, keys)
        assert v is not None and v == v, f"baseline missing {name}"
