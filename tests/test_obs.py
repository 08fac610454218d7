"""Observability subsystem: Chrome-trace schema, Prometheus round trip,
histogram percentiles, the spans and phase counters of each fused round,
zero-cost disabled mode, and the metrics-path regression that a serving
run reports fused == 1."""
import time
import tracemalloc

import numpy as np
import pytest

from repro.obs import NULL_OBS, Obs, make_obs
from repro.obs.metrics import (NULL_REGISTRY, Registry, acceptance_buckets)
from repro.obs.schema import (parse_prometheus_text, validate_chrome_trace,
                              validate_metrics_snapshot)
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving.engine import SchedulerConfig, ServeRequest, ServingEngine

from conftest import tiny_config, tiny_draft_config


def _serve(trace: bool, n_req: int = 5, seed: int = 0):
    se = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                       config=SchedulerConfig(max_batch=2, n_cand=2,
                                              trace=trace))
    se.init_from_seed(0)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_req):
        p = rng.integers(0, 61, int(rng.integers(5, 13))).astype(np.int32)
        r = ServeRequest(i, p, max_new_tokens=int(rng.integers(3, 8)))
        reqs.append(r)
        se.submit(r)
    done = se.run()
    return se, reqs, done


@pytest.fixture(scope="module")
def traced():
    """One trace-enabled serving run shared by the trace assertions."""
    return _serve(trace=True)


# ---------------------------------------------------------------------------
# Chrome trace-event export


def test_chrome_trace_schema(traced):
    se, _, done = traced
    assert len(done) == 5
    trace = se.chrome_trace()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    assert any(e["ph"] == "X" for e in evs)
    assert any(e["ph"] == "i" for e in evs)


def test_trace_tracks_cover_pipeline_phases(traced):
    se, _, _ = traced
    evs = se.chrome_trace()["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    for track in ("round", "target_verify", "draft_generate", "rollback",
                  "prefill", "admit"):
        assert track in names, f"missing {track} track"


def test_trace_ts_dur_sane(traced):
    se, _, _ = traced
    evs = [e for e in se.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert evs
    for e in evs:
        assert e["ts"] >= 0 and e["dur"] >= 0
    # the fused program is one span a round, on the target_verify track
    verify = [e for e in evs if e["name"] == "verify(fused)"]
    assert len(verify) == se.stats()["rounds"]
    assert not [e for e in evs if e["name"] == "draft(fused)"]


def test_virtual_clock_stamped(traced):
    se, _, _ = traced
    evs = [e for e in se.chrome_trace()["traceEvents"]
           if e["ph"] == "X" and "args" in e]
    stamped = [e for e in evs if "virtual_s" in e["args"]]
    assert stamped, "spans should carry the scheduler's virtual clock"


# ---------------------------------------------------------------------------
# the fused round from inside the engine: spans and phase counters


def _spans(trace: dict) -> list:
    """(track, name, start us, end us) of every complete event."""
    evs = trace["traceEvents"]
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    return [(tracks[e["tid"]], e["name"], e["ts"], e["ts"] + e["dur"])
            for e in evs if e["ph"] == "X"]


def test_round_span_holds_each_phase_once(traced):
    se, _, _ = traced
    spans = _spans(se.chrome_trace())
    rounds = [(a, b) for tr, n, a, b in spans
              if (tr, n) == ("round", "round")]
    assert len(rounds) == se.stats()["rounds"]
    for a, b in rounds:
        inside = [(tr, n) for tr, n, s0, s1 in spans
                  if a <= s0 and s1 <= b and (tr, n) != ("round", "round")]
        for phase in (("d2h", "outputs"), ("round", "emit"),
                      ("round", "account")):
            assert inside.count(phase) == 1, (phase, inside)


def _long_engine(n_req: int = 12, **cfg):
    se = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                       config=SchedulerConfig(max_batch=2, n_cand=2,
                                              **cfg))
    se.init_from_seed(0)
    rng = np.random.default_rng(1)
    for i in range(n_req):
        se.submit(ServeRequest(i, rng.integers(1, 61, 8).astype(np.int32),
                               max_new_tokens=int(rng.integers(8, 24))))
    return se


def _counter(se, name: str) -> float:
    return se.metrics()["metrics"]["counters"][name][""]


def test_phase_seconds_add_up_to_round_intervals():
    """Round k's start minus round k-1's is round k-1's dispatch and
    fetch, then the host serial work and admissions before round k."""
    se = _long_engine()
    se.run_step()                     # compiles; admissions before round 1
    admit, rounds = se._phases.admit, []
    while se.has_work():
        a0 = admit.value()
        se.run_step()
        if not se.idle_step:
            rounds.append((se.recorder.ring[-1], admit.value() - a0))
    assert len(rounds) > 20
    assert sum(a for _, a in rounds) > 0      # admissions mid-run
    intervals = phases = 0.0
    for (prev, _), (rec, adm) in zip(rounds, rounds[1:]):
        intervals += rec["t0"] - prev["t0"]
        phases += prev["dispatch_s"] + prev["fetch_s"] + rec["host_s"] + adm
    assert phases == pytest.approx(intervals, rel=0.01)
    assert _counter(se, "serve_fused_rounds_total") == se.stats()["rounds"]


def test_host_serial_excludes_admission():
    se = _long_engine()
    se.run_step()
    prefill = se.engine.prefill_batch

    def slow_prefill(*args, **kwargs):
        time.sleep(0.05)
        return prefill(*args, **kwargs)

    se.engine.prefill_batch = slow_prefill
    a0, h0 = _counter(se, "serve_admit_seconds_total"), \
        _counter(se, "serve_host_serial_seconds_total")
    n0 = se.stats()["rounds"]
    se.run()
    admitted = _counter(se, "serve_admit_seconds_total") - a0
    host = _counter(se, "serve_host_serial_seconds_total") - h0
    assert admitted >= 0.05 * 8               # every later admission
    assert host < 0.05 * (se.stats()["rounds"] - n0) / 4
    assert max(r["host_s"] for r in se.recorder.ring if "host_s" in r) < 0.05


class _SlowArray:
    """A device output whose copy to the host takes 0.2 s longer."""

    def __init__(self, x):
        self.x = x

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.2)
        return np.asarray(self.x, dtype)


@pytest.mark.parametrize("phase", ["dispatch", "fetch", "host"])
def test_stall_counted_in_its_phase(phase):
    """A 0.2 s sleep once, after the stall rule has 64 rounds of
    history, moves that phase's stall counter alone and raises a
    ``stall`` trigger in the flight recorder."""
    se = _long_engine(n_req=24)
    pipe = se.engine.pipeline(se.config.n_cand)
    fused, armed = pipe._fused, [False]

    def due():
        fire, armed[0] = armed[0], False
        return fire

    def slow_fused(*args, **kwargs):
        if phase == "dispatch" and due():
            time.sleep(0.2)
        vout, dout = fused(*args, **kwargs)
        if phase == "fetch" and due():
            vout = dict(vout, tokens=_SlowArray(vout["tokens"]))
        return vout, dout

    def emit_hook(req, tok):
        if phase == "host" and due():
            time.sleep(0.2)

    def stalls():
        return {p: _counter(se, f"serve_stall_{p}_seconds_total")
                for p in ("dispatch", "fetch", "host")}

    pipe._fused, se.emit_hook = slow_fused, emit_hook
    while se.has_work() and se.stats()["rounds"] < 70:
        se.run_step()
    before, n_triggers, armed[0] = stalls(), len(se.recorder.triggers), True
    # a sleep in round 71's emission is host time before round 72
    while se.has_work() and se.stats()["rounds"] < 73:
        se.run_step()
    assert not armed[0]
    moved = {p: v - before[p] for p, v in stalls().items()}
    assert moved.pop(phase) > 0.15
    assert all(v < 0.05 for v in moved.values()), moved
    assert any(t["reason"] == "stall" and t["args"]["phase"] == phase
               for t in se.recorder.triggers[n_triggers:])


def test_stall_counters_start_at_zero():
    """An engine exports its stall counters at 0 before any round, so a
    run without stalls reads 0 rather than a missing counter."""
    counters = _long_engine(n_req=1).metrics()["metrics"]["counters"]
    for p in ("dispatch", "fetch", "host"):
        assert counters[f"serve_stall_{p}_seconds_total"][""] == 0.0


def test_round_record_covers_retirement():
    """The flight recorder's round record is taken after retirement: its
    ``dur_s`` holds a slow emit hook, and its ``tokens_out`` counts the
    requests that finished in that round."""
    se = _long_engine()
    slow = [False]

    def emit_hook(req, tok):
        if slow[0]:
            slow[0] = False
            time.sleep(0.1)

    se.emit_hook = emit_hook
    se.run_step()                     # compiles
    slow[0] = True
    se.run_step()
    assert not slow[0]
    assert se.recorder.ring[-1]["dur_s"] >= 0.1
    finished = 0
    while se.has_work() and not finished:
        before = se.stats()["tokens_out"]
        se.run_step()
        finished = se.stats()["tokens_out"] - before
    assert finished > 0
    assert se.recorder.ring[-1]["tokens_out"] == se.stats()["tokens_out"]


def test_tracing_does_not_retrace_fused(traced):
    """Spans wrap the jit boundary from outside: enabling tracing must
    not change the fused program's shapes or trigger retraces."""
    se, _, _ = traced
    assert se.stats()["fused_compiles"] == 1


def test_metrics_snapshot_schema_and_contents(traced):
    se, _, _ = traced
    rep = se.metrics()
    snap = rep["metrics"]
    assert validate_metrics_snapshot(snap) == []
    # acceptance histogram: integer buckets, measured rate in [0, 1]
    hist = snap["histograms"]["spec_accepted_tokens"][""]
    n_cand = se.config.n_cand
    assert hist["count"] > 0
    rate = hist["sum"] / (hist["count"] * n_cand)
    assert 0.0 <= rate <= 1.0
    # per-tier transfer accounting (admission KV splice is h2d)
    assert snap["counters"]["transfer_bytes_total"]['{tier="h2d"}'] > 0
    assert ('{tier="h2d"}'
            in snap["counters"]["transfer_seconds_total"])
    # paged-KV block gauges, all drained at end of run
    assert snap["gauges"]["kv_blocks"]['{alloc="h0",state="used"}'] == 0


# ---------------------------------------------------------------------------
# satellite regression: fused == 1 through the metrics path


def test_fused_compiles_once_via_metrics_registry():
    """Full serving run (default metrics-on, trace-off config) must
    report exactly one fused trace through the counter registry."""
    se, _, done = _serve(trace=False, n_req=4, seed=3)
    assert len(done) == 4
    snap = se.metrics()["metrics"]
    ctr = snap["counters"]["pipeline_traces_total"]
    assert ctr['{entry="fused"}'] == 1
    assert ctr['{entry="rollback"}'] == 1
    assert (snap["counters"]["serve_fused_rounds_total"][""]
            == se.stats()["rounds"])
    # trace-off mode records no spans
    assert se.chrome_trace()["traceEvents"] == []


# ---------------------------------------------------------------------------
# Prometheus exposition


def test_prometheus_round_trip():
    reg = Registry()
    reg.counter("req_total", "requests").inc(3, tenant="a")
    reg.counter("req_total").inc(1, tenant="b")
    reg.gauge("occupancy", "slots").set(0.625)
    h = reg.histogram("acc", "accepted", buckets=acceptance_buckets(4))
    for v in (0, 1, 1, 4, 2):
        h.observe(v)
    parsed = parse_prometheus_text(reg.prometheus_text())
    assert parsed["req_total"]["type"] == "counter"
    assert parsed["req_total"]["samples"][(("tenant", "a"),)] == 3.0
    assert parsed["req_total"]["samples"][(("tenant", "b"),)] == 1.0
    assert parsed["occupancy"]["samples"][()] == 0.625
    buckets = parsed["acc_bucket"]["samples"]
    assert buckets[(("le", "0"),)] == 1.0          # cumulative
    assert buckets[(("le", "1"),)] == 3.0
    assert buckets[(("le", "4"),)] == 5.0
    assert buckets[(("le", "+Inf"),)] == 5.0
    assert parsed["acc_sum"]["samples"][()] == 8.0
    assert parsed["acc_count"]["samples"][()] == 5.0


def test_prometheus_endpoint_parses(traced):
    se, _, _ = traced
    parsed = parse_prometheus_text(se.prometheus())
    assert "pipeline_traces_total" in parsed
    assert parsed["pipeline_traces_total"]["samples"][
        (("entry", "fused"),)] == 1.0


def test_histogram_percentiles():
    # exact when one bucket holds one distinct value
    reg = Registry()
    h = reg.histogram("x", buckets=acceptance_buckets(4))
    h.observe(2.0)
    assert h.percentile(50) == pytest.approx(2.0)
    # uniform stream: bucket interpolation lands within a bucket width
    h2 = reg.histogram("u", buckets=tuple(np.linspace(0, 1, 21)))
    vals = np.linspace(0.0, 1.0, 201)
    for v in vals:
        h2.observe(float(v))
    width = 0.05
    for p in (10, 50, 90, 99):
        exact = float(np.percentile(vals, p))
        assert abs(h2.percentile(p) - exact) <= width
    assert h2.percentile(0) >= 0.0
    assert h2.percentile(100) == pytest.approx(1.0)


def test_registry_kind_collision_rejected():
    reg = Registry()
    reg.counter("x_total")
    with pytest.raises(TypeError):
        reg.gauge("x_total")


def test_prometheus_label_escaping_round_trip():
    """Label values with quotes, backslashes, newlines and braces must
    survive exposition -> parse (format 0.0.4 escaping)."""
    nasty = 'he"llo\n{x}\\'
    reg = Registry()
    reg.counter("esc_total").inc(7, tenant=nasty, ok="plain")
    reg.histogram("esc_lat", buckets=(1.0,)).observe(0.5, tenant=nasty)
    text = reg.prometheus_text()
    assert '\\"' in text and "\\n" in text and "\\\\" in text
    assert "\n{x}" not in text            # raw newline would split lines
    parsed = parse_prometheus_text(text)
    key = (("ok", "plain"), ("tenant", nasty))
    assert parsed["esc_total"]["samples"][key] == 7.0
    assert parsed["esc_lat_count"]["samples"][(("tenant", nasty),)] == 1.0


def test_histogram_percentile_edge_cases():
    from repro.obs.metrics import DEFAULT_BUCKETS
    reg = Registry()
    # empty series / never-observed labelset -> nan, never a crash
    h = reg.histogram("edge", buckets=acceptance_buckets(4))
    assert np.isnan(h.percentile(50))
    assert np.isnan(h.percentile(50, tenant="ghost"))
    # single observation: every percentile is that observation
    h.observe(3.0)
    for p in (0, 50, 100):
        assert h.percentile(p) == pytest.approx(3.0)
    # all observations in one bucket: clamped to [min, max]
    h2 = reg.histogram("one_bucket", buckets=DEFAULT_BUCKETS)
    for _ in range(50):
        h2.observe(0.042)
    for p in (0, 25, 99, 100):
        assert h2.percentile(p) == pytest.approx(0.042)
    # p=0 -> min, p=100 -> max, both exact
    h3 = reg.histogram("spread", buckets=DEFAULT_BUCKETS)
    for v in (0.002, 0.3, 7.0):
        h3.observe(v)
    assert h3.percentile(0) == pytest.approx(0.002)
    assert h3.percentile(100) == pytest.approx(7.0)


def test_registry_concurrent_snapshot_while_observe():
    """The async front door scrapes snapshot()/prometheus_text() from
    the event loop while the engine thread observes: no exceptions, and
    every histogram snapshot keeps count == +Inf cumulative."""
    import threading

    reg = Registry()
    stop = threading.Event()
    errs: list = []

    def writer():
        i = 0
        try:
            while not stop.is_set():
                # new labelsets force dict growth mid-iteration
                reg.counter("w_total").inc(1, shard=str(i % 37))
                reg.gauge("w_g").set(i, shard=str(i % 11))
                reg.histogram("w_h").observe((i % 100) / 100.0,
                                             shard=str(i % 7))
                i += 1
        except Exception as e:          # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=writer) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            snap = reg.snapshot()
            assert validate_metrics_snapshot(snap) == []
            parse_prometheus_text(reg.prometheus_text())
            for series in snap["histograms"].get("w_h", {}).values():
                assert series["count"] == series["buckets"]["+Inf"]
            reg.histogram("w_h").percentile(99, shard="3")
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert errs == []


# ---------------------------------------------------------------------------
# disabled mode: zero cost, nothing allocated per round


def _null_round(tr, reg):
    """The per-round obs surface the engine loop touches, null-mode."""
    with tr.span("round", "round") as sp:
        sp.fence(None)
        sp.set("k", 1)
        with tr.span("d2h", "outputs"):
            pass
        with tr.span("round", "account"):
            pass
        with tr.span("round", "emit"):
            pass
        sp.rename("idle")
    tr.instant("admit", "admitted")
    tr.complete("draft_generate", "d", 0.0, 1.0, cat="device")
    reg.counter("c_total").inc(1.0, tier="h2d")
    reg.gauge("g").set(2.0)
    reg.histogram("h").observe(0.5)


def test_disabled_tracing_shares_one_span():
    s1 = NULL_TRACER.span("round", "round")
    s2 = NULL_TRACER.span("h2d", "stream", cat="device")
    assert s1 is s2, "disabled spans must be one shared object"
    assert NULL_OBS.enabled is False


def test_disabled_tracing_no_retained_allocations():
    """Disabled-mode obs must not accumulate anything per round: after
    thousands of null rounds, traced memory returns to baseline (an
    enabled tracer retains events — the sensitivity check)."""
    rounds = 5000
    _null_round(NULL_TRACER, NULL_REGISTRY)     # warm call sites
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(rounds):
        _null_round(NULL_TRACER, NULL_REGISTRY)
    grown = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    assert grown < 4096, f"null obs retained {grown} bytes"

    live = Obs(Tracer(fence=False), Registry())
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(rounds):
        _null_round(live.tracer, live.metrics)
    grown_live = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    assert grown_live > 100 * 1024, "sanity: live tracer retains events"

    # with the registry off the engine keeps no phase counters either
    off = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                        config=SchedulerConfig(max_batch=2, metrics=False))
    assert off.obs is NULL_OBS
    assert off._phases is None and off.recorder is None


def test_make_obs_modes():
    assert make_obs(trace=False, metrics=False) is NULL_OBS
    obs = make_obs(trace=True, metrics=False)
    assert obs.tracer.enabled and not obs.metrics.enabled
    assert obs.enabled
