"""System-level behaviour: the serving engine end to end, the
planner/simulator against paper claims, training convergence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MISTRAL_7B, MIXTRAL_8X7B, MIXTRAL_8X22B
from repro.core.planner import ParaSpecPlanner, Policy, Workload
from repro.models import model as M
from repro.sim.hardware import ENV1, ENV2
from repro.sim.simulator import ablation, disk_mode, end_to_end

from conftest import greedy_reference, tiny_config, tiny_draft_config


def test_engine_end_to_end_lossless(jitted):
    """The dual-batch interleaved engine == pure greedy decoding."""
    from repro.serving.engine import (SchedulerConfig, ServeRequest,
                                      ServingEngine)
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=3))
    se.init_from_seed(0)
    B, L, G = 4, 8, 10
    prompts = jax.random.randint(jax.random.PRNGKey(3), (B, L), 0, 61)
    reqs = [ServeRequest(i, np.asarray(prompts[i]), max_new_tokens=G)
            for i in range(B)]
    for r in reqs:
        se.submit(r)
    assert len(se.run()) == B
    ref = np.asarray(greedy_reference(se.engine.tp, tcfg, prompts, G, 64,
                                      jitted))
    for i, r in enumerate(reqs):
        assert (r.result == ref[i]).all(), f"rid {r.rid}"
    assert se.stats()["rounds"] > 0


def test_serving_engine_drains_queue():
    from repro.serving.engine import (SchedulerConfig, ServeRequest,
                                      ServingEngine)
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    se = ServingEngine(tcfg, dcfg,
                       config=SchedulerConfig(max_batch=2, n_cand=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(0)
    for i in range(5):  # deliberately not a multiple of the wave size
        se.submit(ServeRequest(i, rng.integers(0, 61, 8).astype(np.int32),
                               max_new_tokens=4))
    done = se.run()
    assert len(done) == 5
    assert all(len(r.result) == 4 for r in done)
    assert se.pending() == 0


def test_training_learns():
    from repro.data.pipeline import make_lm_batches
    from repro.training.optimizer import make_optimizer
    from repro.training.train_loop import train_loop
    cfg = tiny_config(("attn",), vocab_size=101)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    oi, _ = make_optimizer("adamw")
    data = make_lm_batches(4, 32, cfg.vocab_size)
    _, _, log = train_loop(cfg, params, oi(params), data, 40, lr=3e-3,
                           log_every=39)
    assert log[-1]["loss"] < log[0]["loss"] * 0.7


# ---------------------------------------------------------------------------
# paper-claim regression gates (simulator)


def test_fig5_reproduction_within_tolerance():
    res = end_to_end(MIXTRAL_8X7B, MISTRAL_7B, ENV1, Workload(503, 48, .75),
                     Policy(80, 192, 8, 8))
    spec = res["specoffload"].throughput
    assert abs(spec - 24.74) / 24.74 < 0.20
    assert abs(res["flexgen"].throughput - 9.74) / 9.74 < 0.20
    best = max(r.throughput for k, r in res.items() if k != "specoffload")
    assert 2.0 < spec / best < 3.2          # paper: 2.53x


def test_fig6_utilization_reproduction():
    res = end_to_end(MIXTRAL_8X7B, MISTRAL_7B, ENV1, Workload(503, 48, .75),
                     Policy(80, 192, 8, 8))
    assert abs(res["specoffload"].gpu_util - 0.5867) < 0.12
    ratio = res["specoffload"].gpu_util / res["flexgen"].gpu_util
    assert 3.5 < ratio < 7.0                # paper: 4.49x


def test_table4_ablation_ordering():
    ab = ablation(MIXTRAL_8X7B, MISTRAL_7B, ENV1, Workload(503, 48, .75),
                  Policy(80, 192, 8, 8), Policy(50, 256, 5, 2))
    assert ab["all"].throughput > ab["no_policy"].throughput
    assert ab["all"].throughput > ab["serial_sd"].throughput
    assert ab["all"].throughput > ab["no_sd"].throughput
    assert ab["serial_sd"].throughput > ab["no_sd"].throughput


def test_fig8_disk_ratio():
    dm = disk_mode(MIXTRAL_8X22B, MISTRAL_7B, ENV1, Workload(503, 48, .75),
                   Policy(16, 64, 8, 8))
    assert 0.2 < dm["ratio"] < 0.5          # paper: 0.293


def test_planner_search_beats_bad_policy():
    pl = ParaSpecPlanner(MIXTRAL_8X7B, MISTRAL_7B, ENV1)
    wl = Workload(503, 48, 0.75)
    best = pl.search(wl)
    bad = pl.evaluate(Policy(50, 256, 5, 2), wl)
    assert best.throughput > bad.throughput
    assert best.feasible


# ---------------------------------------------------------------------------
# dry-run artifact gates


def test_dryrun_records_complete_and_compiled():
    from benchmarks.roofline import full_table, load_records
    for mesh in ("single", "multi"):
        recs = load_records(mesh)
        if not recs:
            pytest.skip("dry-run artifacts not generated yet")
        assert len(recs) == 40, f"{mesh}: {len(recs)} records"
        ok = [r for r in recs if r.get("status") == "ok"]
        skip = [r for r in recs if r.get("status") == "skip"]
        assert len(ok) == 33 and len(skip) == 7, (len(ok), len(skip))
        for r in skip:
            assert "long-context" in r["reason"]


def test_roofline_terms_positive_and_bounded():
    from benchmarks.roofline import full_table
    rows = [r for r in full_table("single") if r["dominant"] != "SKIP"]
    if not rows:
        pytest.skip("dry-run artifacts not generated yet")
    for r in rows:
        assert r["t_compute_s"] >= 0 and r["t_memory_s"] > 0
        assert r["t_collective_s"] >= 0
        assert r["dominant"] in ("compute", "memory", "collective")
