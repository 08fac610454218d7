"""Asyncio serving front door: stream parity with the closed loop,
bounded-queue backpressure, weighted tenant fairness, priority
preemption losslessness, and graceful draining."""
import asyncio

import numpy as np
import pytest

from repro.serving.engine import (SchedulerConfig, ServeRequest,
                                  ServingEngine)
from repro.serving.server import AsyncServingServer, RequestRejected
from repro.serving.trace import replay_open_loop, tenant_poisson_requests

from conftest import greedy_reference, tiny_config, tiny_draft_config


def _engine(**kw):
    cfg = dict(max_batch=2, n_cand=2, clock="real", max_len=48)
    cfg.update(kw)
    se = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                       config=SchedulerConfig(**cfg))
    se.init_from_seed(0)
    return se


def _prompts(n, rng, lo=5, hi=13):
    return [rng.integers(0, 61, int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def test_server_requires_real_clock():
    se = _engine(clock="virtual")
    with pytest.raises(ValueError):
        AsyncServingServer(se)


def test_stream_parity_with_closed_loop(jitted):
    """Tokens streamed by the async front door are identical to the
    closed-loop run() output — and to the target-only greedy reference —
    for every request (per-sequence losslessness carries over)."""
    rng = np.random.default_rng(0)
    prompts = _prompts(5, rng)
    gens = [int(g) for g in rng.integers(3, 8, 5)]

    closed = _engine(clock="virtual")
    reqs = [ServeRequest(i, p, g) for i, (p, g) in
            enumerate(zip(prompts, gens))]
    for r in reqs:
        closed.submit(r)
    closed_done = {r.rid: list(map(int, r.result))
                   for r in closed.run()}

    se = _engine()

    async def drive():
        async with AsyncServingServer(se, max_queue=8) as srv:
            handles = [await srv.submit(p, g, rid=i)
                       for i, (p, g) in enumerate(zip(prompts, gens))]
            outs = await asyncio.gather(
                *[srv.collect(h) for h in handles])
        return {h.rid: o for h, o in zip(handles, outs)}

    streamed = asyncio.run(drive())
    assert streamed == closed_done
    for i, (p, g) in enumerate(zip(prompts, gens)):
        ref = greedy_reference(se.engine.tp, se.target_cfg, p[None, :],
                               g, 64, jitted)
        assert streamed[i] == list(map(int, np.asarray(ref)[0]))
    assert not se.has_work()                      # clean drain
    assert se.stats()["fused_compiles"] == 1


def test_backpressure_bounds_queue_and_timeout_rejects():
    """submit() awaits while the bounded admission queue is full; a
    timeout turns starvation into RequestRejected and the rejection
    counter ticks (the engine-level graceful path, reused)."""
    se = _engine(max_batch=1)
    rng = np.random.default_rng(1)
    prompts = _prompts(8, rng)

    async def drive():
        rejected = []
        async with AsyncServingServer(se, max_queue=2,
                                      submit_timeout_s=0.02) as srv:
            handles = []
            for i, p in enumerate(prompts):
                try:
                    handles.append(await srv.submit(p, 6, rid=i))
                except RequestRejected as e:
                    rejected.append(e.reason)
                assert srv._depth() <= 2          # the bound holds
            outs = await asyncio.gather(
                *[srv.collect(h) for h in handles])
        return handles, outs, rejected

    handles, outs, rejected = asyncio.run(drive())
    assert all(r == "backpressure_timeout" for r in rejected)
    assert len(handles) + len(rejected) == len(prompts)
    assert all(len(o) == 6 for o in outs)         # admitted ones finish
    if rejected:
        assert se.obs.metrics.counter(
            "serve_requests_rejected_total").value(
                reason="backpressure_timeout", tenant="default") \
            == len(rejected)


def test_submit_after_drain_rejected():
    se = _engine()

    async def drive():
        srv = AsyncServingServer(se)
        await srv.start()
        h = await srv.submit(np.arange(5, dtype=np.int32), 3)
        toks = await srv.collect(h)
        await srv.drain()
        assert len(toks) == 3
        with pytest.raises(RequestRejected):
            await srv.submit(np.arange(5, dtype=np.int32), 3)

    asyncio.run(drive())


def test_round_error_fails_streams_and_submits():
    """A round that raises (a device error, say) stops the serve loop:
    the open stream, a later submit and drain() all raise that error
    within a timeout instead of waiting forever."""
    se = _engine()
    real_step, calls = se.run_step, []

    def failing_step():
        calls.append(1)
        if len(calls) > 2:
            raise RuntimeError("device lost")
        return real_step()

    se.run_step = failing_step
    prompt = np.arange(5, dtype=np.int32)

    async def drive():
        srv = AsyncServingServer(se, max_queue=8)
        await srv.start()
        h = await asyncio.wait_for(srv.submit(prompt, 20), timeout=30)
        with pytest.raises(RuntimeError, match="device lost"):
            await asyncio.wait_for(srv.collect(h), timeout=30)
        with pytest.raises(RuntimeError, match="device lost"):
            await asyncio.wait_for(srv.submit(prompt, 3), timeout=30)
        with pytest.raises(RuntimeError, match="device lost"):
            await asyncio.wait_for(srv.drain(), timeout=30)

    asyncio.run(drive())
    assert len(calls) == 3                        # no round after the error


def test_weighted_fairness_two_tenants():
    """A flood from tenant A must not starve tenant B: with qos fair
    ordering, B's first admission beats A's backlog even though every
    A request was submitted first."""
    se = _engine(max_batch=1, qos=True,
                 tenant_weights={"a": 1.0, "b": 1.0})
    rng = np.random.default_rng(2)

    async def drive():
        async with AsyncServingServer(se, max_queue=16) as srv:
            a = [await srv.submit(p, 6, tenant="a")
                 for p in _prompts(6, rng)]
            b = [await srv.submit(p, 6, tenant="b")
                 for p in _prompts(2, rng)]
            await asyncio.gather(*[srv.collect(h) for h in a + b])
        return a, b

    a, b = asyncio.run(drive())
    # all of A was queued before any of B, yet B's last admission beats
    # A's last: the fair share interleaved the tenants
    assert max(r.admitted_s for r in b) < max(r.admitted_s for r in a)
    assert all(len(r.result) == 6 for r in a + b)


def test_preemption_lossless_and_prioritized(jitted):
    """A high-priority arrival preempts a long-tail decode (both slots
    busy); the victim is requeued with saved progress and its resumed
    stream still matches the uninterrupted greedy reference exactly."""
    se = _engine(max_batch=1, qos=True, preempt=True,
                 preempt_min_remaining=2, max_len=64)
    rng = np.random.default_rng(3)
    long_p = _prompts(2, rng)
    short_p = _prompts(1, rng)[0]
    longs = [ServeRequest(i, p, 14, priority=2)
             for i, p in enumerate(long_p)]
    short = ServeRequest(9, short_p, 3, priority=0)

    # drive run_step() directly (closed loop) for determinism: fill both
    # slots with low-priority long decodes first
    for r in longs:
        se.submit(r)
    for _ in range(4):
        se.run_step()
    assert se.has_live() and not any(s.done
                                     for half in se._slots for s in half)
    se.submit(short)
    done = se.run()
    assert {r.rid for r in done} | {r.rid for r in []} >= {9}
    victims = [r for r in longs if r.preemptions > 0]
    assert victims, "a long decode should have been preempted"
    assert se.preempted_total == len(victims) >= 1
    # the high-priority request finished before the preempted long one
    assert short.finished_s <= min(r.finished_s for r in victims)
    # losslessness: every stream equals its uninterrupted greedy decode
    for r in longs + [short]:
        ref = greedy_reference(se.engine.tp, se.target_cfg,
                               np.asarray(r.prompt)[None, :],
                               r.max_new_tokens, 64, jitted)
        assert (np.asarray(ref)[0] == r.result).all(), f"rid {r.rid}"
    assert se.stats()["fused_compiles"] == 1


def test_open_loop_replay_multi_tenant():
    """tenant_poisson_requests + replay_open_loop: deterministic tenant
    labeling, token-by-token streaming for every request, per-tenant
    metrics recorded, clean drain."""
    rng = np.random.default_rng(4)
    prompts = _prompts(6, rng)
    tenants = {"acme": {"share": 2.0, "priority": 1},
               "beta": {"share": 1.0, "priority": 0}}
    reqs = tenant_poisson_requests(prompts, 5, 50.0, tenants, seed=5)
    again = tenant_poisson_requests(prompts, 5, 50.0, tenants, seed=5)
    assert [r.tenant for r in reqs] == [r.tenant for r in again]
    assert len({r.tenant for r in reqs}) == 2

    se = _engine(qos=True, preempt=True)

    async def drive():
        async with AsyncServingServer(se, max_queue=8) as srv:
            tokens, handles = await replay_open_loop(srv, reqs,
                                                     speed=50.0)
            report = srv.tenant_report()
        return tokens, handles, report

    tokens, handles, report = asyncio.run(drive())
    assert len(handles) == len(reqs) and not se.has_work()
    assert all(len(t) == 5 for t in tokens.values())
    assert set(report) == {"acme", "beta"}
    assert sum(d["requests"] for d in report.values()) == len(reqs)
    # per-tenant TTFT histogram landed in the registry
    snap = se.metrics()["metrics"]["histograms"]["serve_ttft_seconds"]
    assert sum(s["count"] for s in snap.values()) == len(reqs)
