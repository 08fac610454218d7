"""Tree speculation: layout/ancestor-mask construction, masked kernel vs
reference parity, analytic acceptance model vs brute-force enumeration,
round/pipeline/serving losslessness, and the acceptance metrics."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec_decode import (MAX_TREE_NODES, acceptance_pmf,
                                    acceptance_pmf_tree, expected_generated,
                                    expected_generated_tree,
                                    record_acceptance, spec_round_tree,
                                    tree_layout, tree_n_nodes, tree_spec,
                                    tree_supported)
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.ref import decode_attention_ref, paged_decode_attention_ref
from repro.obs.metrics import Registry
from repro.serving.engine import (SchedulerConfig, ServeRequest,
                                  ServingEngine)

from conftest import greedy_reference, tiny_config


def _attn_draft():
    return tiny_config(("attn",), n_layers=1, d_model=32, n_heads=2,
                       n_kv_heads=1, d_ff=64)


# ---------------------------------------------------------------------------
# layout + ancestor masks


def test_tree_layout_hand_checked():
    lay = tree_layout((3, 2))
    assert lay["n_nodes"] == 10                       # 1 + 3 + 6
    assert lay["depth"].tolist() == [0, 1, 1, 1, 2, 2, 2, 2, 2, 2]
    assert lay["parent"].tolist() == [0, 0, 0, 0, 1, 1, 2, 2, 3, 3]
    assert lay["level_offsets"].tolist() == [0, 1, 4]
    assert lay["first_child"].tolist() == [1, 4, 6, 8, -1, -1, -1, -1,
                                           -1, -1]


@pytest.mark.parametrize("branching", [(1,), (2,), (3, 2), (2, 2, 2),
                                       (4, 1, 2)])
def test_ancestor_mask_vs_walk(branching):
    """anc_mask[i, j] iff j is on the root path of i (or i itself) —
    checked against an explicit parent-pointer walk per node."""
    lay = tree_layout(branching)
    n, parent = int(lay["n_nodes"]), lay["parent"]
    for i in range(n):
        path = {i}
        j = i
        while j != 0:
            j = int(parent[j])
            path.add(j)
        expect = np.zeros(n, bool)
        expect[list(path)] = True
        assert (lay["anc_mask"][i] == expect).all(), f"node {i}"
    # int32 bitmask encodes the same rows
    for i in range(n):
        bits = int(lay["anc_bits"][i])
        got = [(bits >> j) & 1 == 1 for j in range(n)]
        assert got == lay["anc_mask"][i].tolist()


def test_tree_node_cap():
    with pytest.raises(ValueError):
        tree_layout((2,) * 5)                          # 63 nodes > 31
    assert tree_n_nodes((2, 2, 2, 2)) == 31 == MAX_TREE_NODES


def test_tree_spec_levels():
    full = tree_spec((3, 2))
    assert full["prev"] == 0 and full["mask"].shape == (10, 10)
    lvl2 = tree_spec((3, 2), level=2)
    assert lvl2["prev"] == 4 and lvl2["mask"].shape == (6, 10)
    assert lvl2["depths"].tolist() == [2] * 6


def test_tree_supported_gating():
    assert tree_supported(tiny_config(("attn",)))
    assert not tree_supported(tiny_config(("swa",)))
    assert not tree_supported(tiny_config(("attn", "swa")))


# ---------------------------------------------------------------------------
# analytic acceptance model vs brute-force enumeration


@pytest.mark.parametrize("branching,p", [((2,), 0.3), ((3, 2), 0.5),
                                         ((2, 2), 0.7), ((1, 1, 1), 0.4)])
def test_pmf_matches_bruteforce(branching, p):
    """Enumerate every outcome of the per-child i.i.d. Bernoulli(p)
    acceptance model and histogram the reached depth."""
    lay = tree_layout(branching)
    n_children = int(lay["n_nodes"]) - 1
    pmf = np.zeros(len(branching) + 1)
    for bits in itertools.product([0, 1], repeat=n_children):
        prob = np.prod([p if b else 1 - p for b in bits])
        match = {i + 1: b for i, b in enumerate(bits)}
        # greedy acceptance keeps ONE node per level (the target's unique
        # greedy path): walk the first matching child of the current node
        cur, depth = 0, 0
        for d in range(1, len(branching) + 1):
            fc = int(lay["first_child"][cur])
            nxt = next((fc + j for j in range(branching[d - 1])
                        if match[fc + j]), None)
            if nxt is None:
                break
            cur, depth = nxt, d
        pmf[depth] += prob
    np.testing.assert_allclose(np.asarray(acceptance_pmf_tree(p, branching)),
                               pmf, atol=1e-12)
    e_brute = float((pmf * (np.arange(len(pmf)) + 1)).sum())
    assert abs(expected_generated_tree(p, branching) - e_brute) < 1e-12


def test_tree_model_chain_degeneracy():
    """A (1, 1, ..., 1) tree is exactly the linear chain model."""
    for p in (0.2, 0.5, 0.9):
        for m in (1, 3, 5):
            np.testing.assert_allclose(
                np.asarray(acceptance_pmf_tree(p, (1,) * m)),
                np.asarray(acceptance_pmf(p, m)), atol=1e-6)
            assert abs(expected_generated_tree(p, (1,) * m)
                       - expected_generated(p, m)) < 1e-6
    assert expected_generated_tree(1.0, (2, 2)) == 3.0


# ---------------------------------------------------------------------------
# masked kernels vs reference gather (interpret mode)


@pytest.mark.parametrize("branching", [(2,), (3, 2), (2, 2, 2)])
def test_tree_kernel_matches_ref_contiguous(branching):
    lay = tree_layout(branching)
    n = int(lay["n_nodes"])
    b, hq, hkv, d, skv = 3, 4, 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, n, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), jnp.float32)
    lengths = jnp.array([20 + n, 11 + n, 33 + n], jnp.int32)
    out = decode_attention(q, k, v, lengths,
                           anc_bits=jnp.asarray(lay["anc_bits"]),
                           block_k=32, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths, anc_mask=lay["anc_mask"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("branching", [(3, 2), (2, 2, 2)])
def test_tree_kernel_matches_ref_paged(branching):
    lay = tree_layout(branching)
    n = int(lay["n_nodes"])
    b, hq, hkv, d, bs, nb = 3, 4, 2, 16, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, hq, n, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nb, bs, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nb, bs, hkv, d), jnp.float32)
    bt = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 0, 0],
                               [7, 8, 9, 10]], np.int32))
    lengths = jnp.array([30 + n, 17 + n, 40 + n], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lengths,
                                 anc_bits=jnp.asarray(lay["anc_bits"]),
                                 interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lengths,
                                     anc_mask=lay["anc_mask"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# round-level losslessness


@pytest.mark.parametrize("branching", [(2,), (3, 2)])
def test_spec_round_tree_lossless(jitted, branching):
    """Tree-verified emission is token-identical to target-only greedy,
    with both a disagreeing random draft and a fully-agreeing one."""
    from functools import partial
    from repro.models.transformer import init_cache
    tcfg = tiny_config(("attn",))
    tp = M_params(tcfg, 0)
    b, L, steps = 3, 5, 14
    toks = jax.random.randint(jax.random.PRNGKey(7), (b, L), 0,
                              tcfg.vocab_size)
    ref = np.asarray(greedy_reference(tp, tcfg, toks, steps, 96, jitted))
    round_fn = jax.jit(partial(spec_round_tree, sample=False),
                       static_argnames=("target_cfg", "draft_cfg",
                                        "branching", "mesh"))
    for dcfg, dp in ((_attn_draft(), M_params(_attn_draft(), 1)),
                     (tcfg, tp)):
        tc, dc = init_cache(tcfg, b, 96), init_cache(dcfg, b, 96)
        lg, tc = jitted["prefill"](tp, tcfg, toks, tc)
        _, dc = jitted["prefill"](dp, dcfg, toks, dc)
        t_next = jnp.argmax(lg, -1)
        streams = [[int(t)] for t in np.asarray(t_next)]
        for _ in range(steps):
            out = round_fn(tp, tcfg, tc, dp, dcfg, dc, t_next, branching)
            tc, dc, t_next = (out["target_cache"], out["draft_cache"],
                              out["t_next"])
            tr, nr = np.asarray(out["tokens"]), np.asarray(out["n_emitted"])
            for r in range(b):
                streams[r].extend(tr[r, :int(nr[r])].tolist())
        for r in range(b):
            assert streams[r][:steps] == ref[r].tolist(), f"row {r}"
        if dcfg is tcfg:
            # an agreeing draft must be accepted to full depth
            assert (np.asarray(out["n_accept"]) == len(branching)).all()


def test_spec_round_tree_sampled_valid(jitted):
    """Sampled tree acceptance: emitted tokens stay in-vocab, counts in
    range, and the caches stay consistent across rounds."""
    from functools import partial
    from repro.models.transformer import init_cache
    tcfg = tiny_config(("attn",))
    dcfg = _attn_draft()
    tp, dp = M_params(tcfg, 0), M_params(dcfg, 1)
    b, L = 2, 5
    toks = jax.random.randint(jax.random.PRNGKey(9), (b, L), 0,
                              tcfg.vocab_size)
    tc, dc = init_cache(tcfg, b, 96), init_cache(dcfg, b, 96)
    lg, tc = jitted["prefill"](tp, tcfg, toks, tc)
    _, dc = jitted["prefill"](dp, dcfg, toks, dc)
    t_next = jnp.argmax(lg, -1)
    round_fn = jax.jit(partial(spec_round_tree, sample=True),
                       static_argnames=("target_cfg", "draft_cfg",
                                        "branching", "mesh"))
    key = jax.random.PRNGKey(0)
    for i in range(4):
        key, sub = jax.random.split(key)
        out = round_fn(tp, tcfg, tc, dp, dcfg, dc, t_next, (2, 2), key=sub)
        tc, dc, t_next = (out["target_cache"], out["draft_cache"],
                          out["t_next"])
        a = np.asarray(out["n_accept"])
        assert ((0 <= a) & (a <= 2)).all()
        toks_out = np.asarray(out["tokens"])
        n = np.asarray(out["n_emitted"])
        for r in range(b):
            assert (toks_out[r, :n[r]] >= 0).all()
            assert (toks_out[r, :n[r]] < tcfg.vocab_size).all()
    assert int(np.asarray(tc["pos"])[0]) > L


def M_params(cfg, seed):
    from repro.models import model as M
    return M.init_params(cfg, jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# pipeline + serving losslessness (incl. mid-flight paged admission)


def test_tree_pipeline_single_compile_lossless(jitted):
    tcfg = tiny_config(("attn",))
    se = ServingEngine(tcfg, _attn_draft(),
                       config=SchedulerConfig(max_batch=2, spec_tree=(3, 2)))
    se.init_from_seed(0)
    b, L, gen = 4, 6, 10
    prompts = jax.random.randint(jax.random.PRNGKey(3), (b, L), 0,
                                 tcfg.vocab_size)
    ref = np.asarray(greedy_reference(se.engine.tp, tcfg, prompts, gen, 96,
                                      jitted))
    reqs = [ServeRequest(i, np.asarray(prompts[i]), max_new_tokens=gen)
            for i in range(b)]
    for r in reqs:
        se.submit(r)
    assert len(se.run()) == b
    for i, r in enumerate(reqs):
        assert (r.result == ref[i]).all(), f"rid {r.rid}"
    assert se.engine.trace_counts["fused"] == 1
    assert se.engine.trace_counts["rollback"] == 0   # commit is in-fused


def test_tree_pipeline_rejects_swa():
    tcfg = tiny_config(("attn",))
    bad = tiny_config(("swa",))
    with pytest.raises(ValueError):
        ServingEngine(bad, tcfg, config=SchedulerConfig(spec_tree=(2,)))
    with pytest.raises(ValueError):
        ServingEngine(tcfg, bad, config=SchedulerConfig(spec_tree=(2,)))


def test_serving_tree_lossless_midflight_admission(jitted):
    """Tree-mode continuous batching under retirement + mid-flight paged
    admission stays token-identical to target-only greedy decode, with
    exactly one fused compile."""
    tcfg = tiny_config(("attn",))
    se = ServingEngine(tcfg, _attn_draft(),
                       config=SchedulerConfig(max_batch=2, n_cand=2,
                                              spec_tree=(2, 2)))
    se.init_from_seed(0)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(6):                      # 6 reqs > 4 slots: forced churn
        p = rng.integers(0, 61, int(rng.integers(5, 13))).astype(np.int32)
        reqs.append(ServeRequest(i, p,
                                 max_new_tokens=int(rng.integers(3, 10))))
        se.submit(reqs[-1])
    done = se.run()
    assert len(done) == 6 and se.pending() == 0
    st = se.stats()
    assert st["fused_compiles"] == 1
    assert st["spec_mode"] == "tree" and st["spec_tree"] == (2, 2)
    for r in reqs:
        ref = greedy_reference(se.engine.tp, tcfg,
                               np.asarray(r.prompt)[None, :],
                               r.max_new_tokens, 96, jitted)
        assert (np.asarray(ref)[0] == r.result).all(), f"rid {r.rid}"
    kv = se.kv_stats()
    assert kv["paged"] and all(a["used"] == 0 for a in kv["allocators"])
    prom = se.prometheus()
    assert 'spec_tokens_wasted_total{mode="tree"}' in prom
    assert "spec_accept_depth_total" in prom


def test_serving_tree_acceptance_replan():
    """The acceptance-drift trigger runs the joint chain-vs-tree search
    and records the suggested tree budget."""
    tcfg = tiny_config(("attn",))
    se = ServingEngine(tcfg, _attn_draft(),
                       config=SchedulerConfig(max_batch=2, n_cand=2,
                                              spec_tree=(2, 2),
                                              replan_accept_drift=0.05,
                                              replan_interval=2))
    se.init_from_seed(0)
    rng = np.random.default_rng(1)
    for i in range(3):
        se.submit(ServeRequest(i, rng.integers(0, 61, 8).astype(np.int32),
                               12))
    se.run()
    # a random tiny draft accepts ~never: the measured-acceptance EMA
    # decays away from the planned 0.7 and crosses the 0.05 drift band
    assert len(se.replan_events) >= 1
    ev = se.replan_events[0]
    assert "tree" in ev and "accept_rate" in ev
    assert ev["accept_rate"] < 0.7 - 0.05
    assert se.suggested_policy is not None


# ---------------------------------------------------------------------------
# metrics


def test_record_acceptance_tree_counters():
    reg = Registry()
    # two sequences, depth cap 2, 6 candidates verified per round (tree
    # (2,2) has 7 nodes -> 6 non-root candidates)
    record_acceptance(reg, np.array([2, 0]), 2, n_draft=6, mode="tree")
    snap = reg.snapshot()
    c = snap["counters"]
    assert c["spec_tokens_accepted_total"]['{mode="tree"}'] == 2.0
    assert c["spec_tokens_wasted_total"]['{mode="tree"}'] == 10.0
    assert c["spec_verify_rounds_total"]['{mode="tree"}'] == 2.0
    depth = c["spec_accept_depth_total"]
    assert depth['{depth="1",mode="tree"}'] == 1.0
    assert depth['{depth="2",mode="tree"}'] == 1.0
