"""The routed expert FFN of a serving MoE layer scan on the CPU: the rule
that selects it, its equality with the dense dispatch path, the training
forward that keeps the dense path, and the draft proposals of the
Kimi-K2 / Moonlight pair under either path.

The routed path reads only the experts some token chose
(``kernels/moe_ffn.py``, in interpret mode here); the dense path
dispatches into an (E, N, D) buffer and computes every expert.  Both do
the same float32 math in a different summation order: 1e-5 absolute on
O(1) outputs.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import model_config, tiny_config, tiny_mla_pair
from repro.configs import PAIRS
from repro.core.spec_decode import draft_generate
from repro.models import model as M
from repro.models import moe
from repro.models.transformer import init_cache
from repro.serving.engine import SchedulerConfig, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import weights  # noqa: E402

TOL = 1e-5
_, D_DOC = tiny_mla_pair()
DRAFT = model_config(D_DOC)
MOONLIGHT = PAIRS["kimi-k2-moonlight-v5e-pair"][1]


@pytest.mark.parametrize("n,e,k,router,dropless,want", [
    (16, 64, 6, 64, True, True),      # Moonlight draft step: E(1-k/E)^N ~ 13
    (80, 8, 2, 8, True, False),       # Mixtral-8x22B verify: ~1e-9
    (80, 8, 8, 384, True, False),     # Kimi-K2 target's expert share
    (16, 8, 8, 384, True, False),     # a share, even at few tokens
    (512, 64, 6, 64, True, False),    # prefill
    (16, 64, 6, 64, False, False),    # a call that may drop tokens
])
def test_selection_rule(n, e, k, router, dropless, want):
    assert moe.routed_ffn(n, e, k, router, dropless) is want


def _layer(e, k, dm, f, n_shared, sigmoid, seed):
    p = moe.init_moe(jax.random.PRNGKey(seed), dm, f, e, "swiglu",
                     jnp.float32, router_experts=e, n_shared=n_shared,
                     score_bias=sigmoid)
    if sigmoid:   # drawn as the benchmark draws it: std 0.5
        p["score_bias"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), (e,))
    return p


@pytest.mark.parametrize("e,k,n,dm,f,n_shared,sigmoid", [
    (64, 6, 16, 64, 1408, 2, True),   # Moonlight's routing at reduced D
    (64, 6, 4, 64, 1408, 2, True),    # fewer draft slots
    (8, 2, 4, 32, 64, 0, False),      # softmax top-2, no shared experts
])
def test_routed_path_equals_dense_path(monkeypatch, e, k, n, dm, f,
                                       n_shared, sigmoid):
    p = _layer(e, k, dm, f, n_shared, sigmoid, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(9), (n, dm))
    kw = dict(n_experts=e, top_k=k, capacity_factor=float("inf"),
              activation="swiglu", routed_scaling_factor=2.446)
    assert moe.routed_ffn(n, e, k, e)
    calls = []
    routed_fn = moe._routed_expert_ffn
    monkeypatch.setattr(moe, "_routed_expert_ffn",
                        lambda *a: calls.append(1) or routed_fn(*a))
    # as the serving layer scan hands a routed layer over: expert weights
    # stacked over the scan's layers (here two, the second read) and the
    # layer's index
    stacked = dict(p, layer=1, **{
        w: jnp.stack([jnp.zeros_like(p[w]), p[w]])
        for w in moe.EXPERT_WEIGHTS})
    got = jax.jit(lambda p, x: moe._moe_local(p, x, **kw))(stacked, x)
    assert calls, "the routed path was not taken"
    want = jax.jit(lambda p, x: moe._moe_local(p, x, **kw))(p, x)
    assert len(calls) == 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)


def _proposals(cfg, b, n_cand=4, prompt_len=12, seed=5):
    """Greedy draft proposals of ``b`` slots after a prefill, on the
    benchmark's weights."""
    tp, dp = weights.build(M.init_params, cfg, cfg, seed)
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, prompt_len)),
                          jnp.int32)
    _, cache = M.prefill(dp, cfg, prompts, init_cache(cfg, b, 32))
    t_next = jnp.asarray(rng.integers(1, cfg.vocab_size, b), jnp.int32)
    # a fresh function: jit would share a trace of draft_generate itself
    gen = jax.jit(lambda p, c, t: draft_generate(p, cfg, c, t, n_cand))
    drafts, logits, _, _ = gen(dp, cache, t_next)
    return np.asarray(drafts), np.asarray(logits)


# the conftest Moonlight-shaped draft (8 experts, top-2) and one with
# Moonlight's own routing widths (64 experts, top-6) and 16 slots
DRAFTS = {"tiny-moonlight": (DRAFT, 4),
          "moonlight-routing": (dataclasses.replace(
              DRAFT, n_experts=MOONLIGHT.n_experts,
              router_experts=MOONLIGHT.router_experts,
              top_k=MOONLIGHT.top_k), 16)}


@pytest.mark.parametrize("name", sorted(DRAFTS))
def test_kimi_draft_proposals_equal_under_both_paths(monkeypatch, name):
    """The draft's proposals are token for token the same whether its
    decode steps take the routed or the dense path.  The benchmark's
    served tokens do not depend on the draft at acceptance 0, so this is
    what guards the draft's routed path; the logits behind the proposals
    agree to float32 summation order too (a dropped expert moves them by
    1e-3 or more, yet may flip no proposal)."""
    cfg, b = DRAFTS[name]
    calls = []
    routed_fn = moe._routed_expert_ffn
    monkeypatch.setattr(moe, "_routed_expert_ffn",
                        lambda *a: calls.append(1) or routed_fn(*a))
    routed, routed_logits = _proposals(cfg, b)
    assert calls, "the routed path was not taken"
    monkeypatch.setattr(moe, "routed_ffn", lambda *a, **k: False)
    dense, dense_logits = _proposals(cfg, b)
    np.testing.assert_array_equal(routed, dense)
    np.testing.assert_allclose(routed_logits, dense_logits, atol=TOL,
                               rtol=TOL)


def test_training_forward_keeps_the_dense_path():
    """The cache-less (training) forward of a dropless MoE at a routed
    shape (2 tokens, top-2 of 4: E(1-k/E)^N = 1) stays dense, so its
    loss can be differentiated (the routed kernel has no VJP)."""
    cfg = tiny_config(("attn",), "moe", None, n_experts=4, top_k=2,
                      moe_dropless=True)
    assert moe.routed_ffn(2, 4, 2, 4)
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray([[3, 7]], jnp.int32)}
    grads = jax.grad(lambda p: M.loss_fn(p, cfg, batch))(p)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree.leaves(grads))


def test_engine_gauge_marks_the_routed_model():
    """``moe_routed_ffn``: the tiny Kimi-K2 target holds an expert share
    (dense), its draft's 2-slot steps take the routed path."""
    t_doc, _ = tiny_mla_pair()
    eng = ServingEngine(model_config(t_doc), DRAFT,
                        config=SchedulerConfig(max_batch=2))
    g = eng.metrics()["metrics"]["gauges"]["moe_routed_ffn"]
    assert g == {'{model="target"}': 0.0, '{model="draft"}': 1.0}


def _cell_pairs():
    doc = json.loads((ROOT / "bench/configs/mixtral-8x22b-v5e-pair.json")
                     .read_text())
    return {"kimi-k2.offline": PAIRS["kimi-k2-moonlight-v5e-pair"],
            "mixtral-8x22b.offline": tuple(model_config(doc[k])
                                           for k in ("target", "draft"))}


@pytest.mark.parametrize("cell,target_routed,draft_routed", [
    ("kimi-k2.offline", False, True),
    ("mixtral-8x22b.offline", False, False),
])
def test_benchmark_cells_select(cell, target_routed, draft_routed):
    """At the cells' 16 slots a half and 4 candidates: only the Moonlight
    draft's steps are routed (the Kimi-K2 target holds a share, the
    8x22B target verifies 80 tokens over 8 experts, Mistral is dense)."""
    target, draft = _cell_pairs()[cell]
    sel = lambda c, n: c.is_moe and moe.routed_ffn(
        n, c.n_experts, c.top_k, c.router_experts)
    assert sel(target, 16 * 5) == target_routed
    assert sel(draft, 16) == draft_routed
