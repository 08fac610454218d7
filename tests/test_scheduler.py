"""Continuous-batching scheduler: EOS retirement, mid-flight admission,
losslessness, shape-stable compilation, metrics, planner occupancy hook."""
import numpy as np
import pytest

from repro.core.planner import ParaSpecPlanner, Policy, Workload
from repro.configs.base import MISTRAL_7B, MIXTRAL_8X7B
from repro.serving.engine import (SchedulerConfig, ServeRequest,
                                  ServingEngine, latency_percentiles)
from repro.serving.trace import poisson_arrivals, poisson_requests
from repro.sim.hardware import ENV1

from conftest import greedy_reference, tiny_config, tiny_draft_config


@pytest.fixture(scope="module")
def served():
    """One engine run shared by the admission/losslessness assertions:
    7 requests with mixed prompt lengths and max_new_tokens through a
    2-slot-per-half engine (capacity 4 < queue 7), so sequences retire
    at their own lengths and queued requests join freed slots mid-run."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(7):
        p = rng.integers(0, 61, int(rng.integers(5, 13))).astype(np.int32)
        r = ServeRequest(i, p, max_new_tokens=int(rng.integers(3, 10)))
        reqs.append(r)
        se.submit(r)
    done = se.run()
    return se, reqs, done


def test_midflight_admission_completes_all(served):
    se, reqs, done = served
    assert len(done) == len(reqs)
    assert se.pending() == 0
    # queue exceeded capacity, so someone had to wait for a freed slot
    assert any(r.queue_s > 0 for r in reqs)


def test_uneven_max_new_tokens_respected(served):
    _, reqs, _ = served
    lens = {r.rid: len(r.result) for r in reqs}
    assert len(set(r.max_new_tokens for r in reqs)) > 1
    for r in reqs:
        assert lens[r.rid] == r.max_new_tokens  # eos_id=-1: exact length


def test_losslessness_per_sequence(served, jitted):
    """Admission into a mid-flight batch must not perturb any sequence:
    every emitted stream equals a target-only greedy decode of that
    prompt alone."""
    se, reqs, _ = served
    tcfg = se.target_cfg
    for r in reqs:
        ref = greedy_reference(se.engine.tp, tcfg,
                               np.asarray(r.prompt)[None, :],
                               r.max_new_tokens, 64, jitted)
        assert (np.asarray(ref)[0] == r.result).all(), f"rid {r.rid}"


def test_fused_step_compiles_once(served):
    """Slot retirement/admission must never change the fused step's
    shapes — one trace for the whole serving lifetime."""
    se, _, _ = served
    assert se.engine.trace_counts["fused"] == 1
    assert se.engine.trace_counts["rollback"] == 1


def test_metrics_recorded(served):
    se, reqs, done = served
    st = se.stats()
    assert st["rounds"] > 0 and st["wall_s"] > 0
    assert 0.0 < st["mean_occupancy"] <= 1.0
    assert se.throughput(done) > 0
    for r in reqs:
        assert r.ttft_s >= r.queue_s >= 0
        assert r.latency_s >= r.ttft_s
        assert r.tok_per_s > 0
    pct = latency_percentiles(done, "latency_s")
    assert pct["p50"] <= pct["p95"] <= pct["p99"]


def test_eos_early_retirement(jitted):
    """A sequence retires the moment it emits EOS — and the truncated
    stream still matches the greedy reference up to (and including) it."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(42)
    prompt = rng.integers(0, 61, 9).astype(np.int32)
    gen = 10
    ref = np.asarray(greedy_reference(se.engine.tp, tcfg, prompt[None, :],
                                      gen, 64, jitted))[0]
    # pick the token the target greedily emits mid-stream as the EOS id
    k = 4
    eos = int(ref[k])
    stop = int(np.where(ref == eos)[0][0])  # first occurrence wins
    se.config.eos_id = eos
    se.submit(ServeRequest(0, prompt, max_new_tokens=gen))
    # a second request with a different (absent) suffix runs to full length
    p2 = rng.integers(0, 61, 7).astype(np.int32)
    r2 = ServeRequest(1, p2, max_new_tokens=6)
    se.submit(r2)
    done = se.run()
    r1 = next(r for r in done if r.rid == 0)
    assert len(r1.result) == stop + 1 < gen
    assert (r1.result == ref[:stop + 1]).all()
    ref2 = np.asarray(greedy_reference(se.engine.tp, tcfg, p2[None, :],
                                       6, 64, jitted))[0]
    exp2 = ref2
    hits = np.where(ref2 == eos)[0]
    if hits.size:
        exp2 = ref2[:int(hits[0]) + 1]
    assert (r2.result == exp2).all()


def test_queue_longer_than_capacity_with_arrivals():
    """Poisson trace with queue length >> batch capacity: everything
    completes, arrivals are honored (no TTFT before arrival), and the
    engine keeps occupancy meaningful."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 61, 8).astype(np.int32) for _ in range(9)]
    reqs = poisson_requests(prompts, 4, rate_rps=50.0, seed=7)
    for r in reqs:
        se.submit(r)
    done = se.run()
    assert len(done) == 9 and se.pending() == 0
    for r in reqs:
        assert len(r.result) == 4
        assert r.admitted_s >= r.arrival_s
        assert r.first_token_s >= r.admitted_s
    st = se.stats()
    assert 0.0 < st["mean_occupancy"] <= 1.0
    assert st["fused_compiles"] == 1


def test_sjf_admission_prefers_short_jobs():
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=1, n_cand=2,
                                              admission="sjf"))
    se.init_from_seed(0)
    rng = np.random.default_rng(5)
    # submitted long-first; SJF should finish the short ones earlier
    lens = [12, 3, 3, 12]
    for i, g in enumerate(lens):
        se.submit(ServeRequest(i, rng.integers(0, 61, 6).astype(np.int32),
                               max_new_tokens=g))
    done = se.run()
    assert len(done) == 4
    short_done = max(r.finished_s for r in done if r.max_new_tokens == 3)
    long_done = max(r.finished_s for r in done if r.max_new_tokens == 12)
    assert short_done < long_done


def test_engine_reusable_across_runs():
    """Halves and compiled programs persist: a second submit/run cycle
    reuses the same fused program."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=2,
                                              max_len=40))
    se.init_from_seed(0)
    rng = np.random.default_rng(9)
    se.submit(ServeRequest(0, rng.integers(0, 61, 8).astype(np.int32), 4))
    d1 = se.run()
    se.submit(ServeRequest(1, rng.integers(0, 61, 8).astype(np.int32), 5))
    d2 = se.run()
    assert len(d1) == 1 and len(d2) == 1
    assert se.stats()["fused_compiles"] == 1


def test_contiguous_mode_regression(jitted):
    """SchedulerConfig(paged=False) keeps the original per-slot
    (B, max_len) splice path working and lossless."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=2,
                                              paged=False))
    se.init_from_seed(0)
    rng = np.random.default_rng(21)
    reqs = [ServeRequest(i, rng.integers(0, 61, 8).astype(np.int32), 5)
            for i in range(3)]
    for r in reqs:
        se.submit(r)
    done = se.run()
    assert len(done) == 3
    assert se.kv_stats()["paged"] is False
    for r in reqs:
        ref = greedy_reference(se.engine.tp, tcfg,
                               np.asarray(r.prompt)[None, :], 5, 64, jitted)
        assert (np.asarray(ref)[0] == r.result).all()


def test_submit_rejects_oversized_request_gracefully():
    """A request that could never fit the KV budget is refused — not
    crashed on: submit() returns False, stamps the reason, and the
    rejection counter ticks, so live serving just moves on."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=1, n_cand=2,
                                              max_len=32))
    se.init_from_seed(0)
    big = ServeRequest(0, np.zeros(30, np.int32), 8)   # needs 51 > 32
    assert se.submit(big) is False
    assert big.rejected == "never_fits"
    assert se.pending() == 0 and se.rejected_total == 1
    assert se.obs.metrics.counter(
        "serve_requests_rejected_total").value(
            reason="never_fits", tenant="default") == 1
    assert se.stats()["rejected"] == 1
    # a fitting request on the same engine is still served
    ok = ServeRequest(1, np.zeros(6, np.int32), 4)
    assert se.submit(ok) is True
    assert len(se.run()) == 1 and len(ok.result) == 4


def test_submit_rejects_when_bounded_queue_full():
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=1, n_cand=2,
                                              max_queue=2))
    se.init_from_seed(0)
    reqs = [ServeRequest(i, np.zeros(6, np.int32), 3) for i in range(3)]
    assert se.submit(reqs[0]) and se.submit(reqs[1])
    assert se.submit(reqs[2]) is False
    assert reqs[2].rejected == "queue_full"
    assert se.pending() == 2


def test_multi_run_clock_monotonic():
    """Regression for the virtual-clock reset bug: a max_rounds-
    exhausted run() leaves a request queued; the next run() must NOT
    rebase the clock underneath it.  Every stamp stays non-negative and
    completion times are non-decreasing across the two runs."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=1, n_cand=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(11)
    early = ServeRequest(0, rng.integers(0, 61, 6).astype(np.int32), 8)
    # arrives far in the future (beyond any jit-compile wall charge) so
    # the first run() exhausts max_rounds with it still queued on the
    # old clock; the idle fast-forward covers the gap in run 2
    late = ServeRequest(1, rng.integers(0, 61, 6).astype(np.int32), 4,
                        arrival_s=1e4)
    se.submit(early)
    se.submit(late)
    first = se.run(max_rounds=2)
    assert se.pending() >= 1          # `late` still queued
    clock_before = se.now()
    # a fresh submission between runs lands on the same live clock
    fresh = ServeRequest(2, rng.integers(0, 61, 6).astype(np.int32), 4)
    se.submit(fresh)
    done = first + se.run()
    # never rebased under the queue: run-2 admissions continue past the
    # run-1 clock (a reset would stamp `fresh` near zero again)
    assert fresh.admitted_s >= clock_before
    assert len(done) == 3
    for r in (early, late, fresh):
        assert r.admitted_s >= r.arrival_s >= 0.0
        assert r.queue_s >= 0.0 and r.ttft_s >= 0.0
        assert r.latency_s >= 0.0
    fins = [r.finished_s for r in done]   # retirement order
    assert all(a <= b for a, b in zip(fins, fins[1:]))
    # a fully drained engine still starts the next trace at t=0
    assert not se.has_work()
    replay = ServeRequest(3, rng.integers(0, 61, 6).astype(np.int32), 3)
    se.submit(replay)
    se.run()
    assert replay.admitted_s < late.arrival_s


def test_windowed_throughput_attribution():
    """throughput(done) over a subset divides by the wall time of the
    run window(s) that served it — not the engine's lifetime wall.
    Regression for the subset-over-full-wall underreporting bug."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(13)

    def batch(base, n=3, gen=5):
        rs = [ServeRequest(base + i,
                           rng.integers(0, 61, 6).astype(np.int32), gen)
              for i in range(n)]
        for r in rs:
            se.submit(r)
        return rs

    a = batch(0)
    done_a = se.run()
    b = batch(10)
    done_b = se.run()
    assert len(done_a) == len(done_b) == 3
    assert len(se._windows) == 2
    toks_a = sum(len(r.result) for r in a)
    toks_b = sum(len(r.result) for r in b)
    # each subset is attributed exactly its own run's wall window
    assert se.throughput(done_a) == pytest.approx(
        toks_a / se._windows[0])
    assert se.throughput(done_b) == pytest.approx(
        toks_b / se._windows[1])
    # lifetime view still spans everything
    assert se.throughput() == pytest.approx(
        (toks_a + toks_b) / se.stats()["wall_s"])
    # run-2 subset rate is NOT diluted by run 1's wall time
    assert se.throughput(done_b) > toks_b / se.stats()["wall_s"]


# ---------------------------------------------------------------------------
# planner effective-occupancy term


def test_planner_occupancy_scales_throughput():
    pl = ParaSpecPlanner(MIXTRAL_8X7B, MISTRAL_7B, ENV1)
    pol = Policy(80, 192, 8, 8)
    full = pl.evaluate(pol, Workload(503, 48, 0.75, occupancy=1.0))
    half = pl.evaluate(pol, Workload(503, 48, 0.75, occupancy=0.5))
    assert half.throughput < full.throughput
    # decode rounds still pay full-slot compute, so useful throughput
    # falls at least as fast as occupancy on the decode-bound side
    assert half.throughput < full.throughput * 0.75


def test_planner_search_with_occupancy_feasible():
    pl = ParaSpecPlanner(MIXTRAL_8X7B, MISTRAL_7B, ENV1)
    rep = pl.search(Workload(503, 48, 0.75, occupancy=0.4))
    assert rep.feasible and rep.throughput > 0


def test_planner_kv_bytes_per_seq_term():
    """Measured resident-KV bytes (int8 + block-rounded) shrink the
    host-attention KV traffic term, never the compute terms."""
    from repro.core.planner import stored_kv_bytes_per_seq
    cfg = MIXTRAL_8X7B
    ctx = 503 + 24
    bf16 = stored_kv_bytes_per_seq(cfg, ctx)
    int8 = stored_kv_bytes_per_seq(cfg, ctx, quant=True)
    paged = stored_kv_bytes_per_seq(cfg, ctx, block_size=16)
    assert int8 < bf16                      # 1B + scales beats 2B values
    assert paged >= bf16                    # fragmentation rounds up
    pl = ParaSpecPlanner(cfg, MISTRAL_7B, ENV1)
    pol = Policy(80, 192, 8, 8)
    base = pl.evaluate(pol, Workload(503, 48, 0.75))
    quant = pl.evaluate(pol, Workload(503, 48, 0.75,
                                      kv_bytes_per_seq=int8))
    assert quant.detail["t_attn_host"] <= base.detail["t_attn_host"]
    assert quant.throughput >= base.throughput


def test_online_replan_fires_on_occupancy_drift():
    """With a tight drift threshold and low real occupancy, the engine
    re-runs the ParaSpec search and records a suggested policy."""
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=4, n_cand=2,
                                              replan_threshold=0.2,
                                              replan_interval=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(11)
    # one request in an 8-slot engine -> occupancy 1/8, far from planned 1.0
    se.submit(ServeRequest(0, rng.integers(0, 61, 8).astype(np.int32), 12))
    se.run()
    assert se.replan_events, "occupancy drift should trigger a re-search"
    assert se.suggested_policy is not None
    assert se.replan_events[0]["occupancy"] < 0.5


# ---------------------------------------------------------------------------
# trace helpers


def test_poisson_arrivals_monotone():
    arr = poisson_arrivals(5.0, 100, seed=1)
    assert (np.diff(arr) > 0).all()
    assert abs(np.mean(np.diff(arr)) - 0.2) < 0.1
