"""Multi-head latent attention (MLA) and the DeepSeek-V3 expert layer on the
CPU at a small size, against ``bench/reference_mla.py`` (float32, written
from the published definitions, nothing of the program's).

Tolerances: float32 program against the float32 reference at
``precision="highest"``; the programs differ only in summation order
(absorbed vs decompressed attention, dispatch vs dense experts), so
differences are a few float32 ulps of O(1) logits (5e-6 measured):
1e-4 absolute.  The same program in bfloat16 reads 0.08-0.09 there
(``test_served_logits_match_reference``'s bfloat16 case).
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import model_config, tiny_mla_pair
from repro.configs import PAIRS
from repro.configs.base import ModelConfig
from repro.core.interleave import fused_verify_and_draft
from repro.core.spec_decode import draft_generate, greedy_acceptance
from repro.kernels import ops
from repro.kernels.ref import paged_mla_attention_ref
from repro.models import mla as mla_lib
from repro.models import model as M
from repro.models.moe import _moe_local, init_moe
from repro.models.transformer import (admit_sequence_paged, init_cache,
                                      init_paged_cache)
from repro.serving.engine import SchedulerConfig, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import weights  # noqa: E402
from bench.check import reference_module  # noqa: E402

REF = reference_module({"reference": "bench/reference_mla.py"})
T_DOC, D_DOC = tiny_mla_pair()
TARGET, DRAFT = model_config(T_DOC), model_config(D_DOC)
TOL = 1e-4


def _params(seed=5, dtype=None):
    t, d = TARGET, DRAFT
    if dtype:
        t, d = (dataclasses.replace(c, dtype=dtype) for c in (t, d))
    tp, dp = weights.build(M.init_params, t, d, seed)
    return t, d, tp, dp


# ---------------------------------------------------------------------------
# (a) absorbed decode == decompressed attention


@pytest.mark.parametrize("paged", [False, True])
def test_absorbed_decode_equals_decompressed(paged):
    """Prefill (decompressed per-head K/V) of the whole sequence against
    prefill of a prefix then an absorbed decode of the last 4 tokens over
    the latent cache, contiguous or paged."""
    cfg = TARGET
    p = jax.tree.map(lambda a: a[0], weights.build(
        M.init_params, cfg, DRAFT, 1)[0]["layers"][0]["attn"])
    n, m, bs = 19, 4, 8
    x = jax.random.normal(jax.random.PRNGKey(2), (2, n + m, cfg.d_model))
    want, _ = mla_lib.apply_mla(p, x, cfg, phase="prefill")
    cache = init_cache(cfg, 2, 32)["layers"][0]
    cache = jax.tree.map(lambda a: a[0], cache)
    _, cache = mla_lib.apply_mla(p, x[:, :n], cfg, cache=cache,
                                 phase="prefill")
    bt = None
    if paged:
        mbs = 32 // bs
        rows = cache["latent"].reshape(2 * mbs, bs, 1, -1)
        pool = jnp.concatenate([jnp.zeros_like(rows[:1]), rows])
        cache = {"latent": pool}
        bt = jnp.arange(1, 1 + 2 * mbs, dtype=jnp.int32).reshape(2, mbs)
    got, _ = mla_lib.apply_mla(p, x[:, n:], cfg, cache=cache,
                               pos=jnp.full((2,), n, jnp.int32),
                               phase="decode", block_tables=bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, n:]),
                               atol=TOL, rtol=TOL)


def test_latent_kernel_matches_oracle_in_interpret_mode():
    """The latent-mode paged kernel against the gather oracle: slots of
    several page groups, one empty, rows past each length poisoned with
    NaN (the kernel must not read them into a result)."""
    rng = np.random.default_rng(0)
    b, h, m, r, v, bs, mbs = 4, 4, 3, 640, 512, 16, 40
    pool = rng.standard_normal((1 + b * mbs, bs, 1, r)).astype(np.float32)
    bt = (1 + rng.permutation(b * mbs).reshape(b, mbs)).astype(np.int32)
    lengths = np.array([5, 0, 600, 33], np.int32)
    poisoned = pool.copy()
    for i, n in enumerate(lengths):
        for j in range(mbs):
            lo = max(n - j * bs, 0)
            if lo < bs:
                poisoned[bt[i, j], lo:] = np.nan
    q = jnp.asarray(rng.standard_normal((b, h, m, r)), jnp.float32)
    got = ops.paged_mla_attention(q, jnp.asarray(poisoned), jnp.asarray(bt),
                                  jnp.asarray(lengths), v_dim=v, scale=0.05,
                                  interpret=True)
    want = paged_mla_attention_ref(q, jnp.asarray(pool), jnp.asarray(bt),
                                   jnp.asarray(lengths), v_dim=v, scale=0.05)
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], atol=1e-5)


# ---------------------------------------------------------------------------
# (b) the served path against the reference


def _served_logits(t, d, tp, dp, prompt, feed, n_cand):
    """Admission prefill into a contiguous cache, the graft into the
    latent block pool, the target's verify of ``feed`` through the pool
    (the call the fused round makes), and the draft's chain of
    ``n_cand`` proposals from its contiguous cache."""
    n, bs = len(prompt), 8
    mbs = -(-(n + len(feed)) // bs)
    toks = jnp.asarray(prompt)[None]
    lg0, cache = jax.jit(M.prefill, static_argnums=1)(
        tp, t, toks, init_cache(t, 1, mbs * bs))
    paged = jax.jit(admit_sequence_paged, static_argnums=0)(
        t, init_paged_cache(t, 1, 1 + mbs, bs, mbs), cache, 0,
        jnp.arange(1, mbs + 1, dtype=jnp.int32), n, 0)
    lg1, _, _ = jax.jit(M.decode, static_argnums=1)(
        tp, t, paged, jnp.asarray(feed)[None])
    lgd, dcache = jax.jit(M.prefill, static_argnums=1)(
        dp, d, toks, init_cache(d, 1, mbs * bs))
    drafts, dlog, _, _ = jax.jit(draft_generate, static_argnums=(1, 4))(
        dp, d, dcache, jnp.asarray(feed[:1]), n_cand)
    return (np.concatenate([np.asarray(lg0), np.asarray(lg1[0])]),
            np.asarray(drafts[0]), np.asarray(dlog[0]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_served_logits_match_reference(dtype):
    """Prefill, then paged verify of 5 tokens (target) and a chain of 4
    proposals (draft), against the reference's full forward pass; in
    bfloat16 the same program misses the tolerance."""
    t, d, tp, dp = _params(dtype=dtype)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, 128, 21).astype(np.int32)
    feed = rng.integers(1, 128, 5).astype(np.int32)
    got_t, drafts, got_d = _served_logits(t, d, tp, dp, prompt, feed, 4)
    want_t = np.asarray(REF.logits(tp, T_DOC,
                                   np.concatenate([prompt, feed])))[20:]
    chain = np.concatenate([prompt, feed[:1], drafts[:-1]])
    want_d = np.asarray(REF.logits(dp, D_DOC, chain))[21:]
    err = max(np.abs(got_t - want_t).max(), np.abs(got_d - want_d).max())
    if dtype == "float32":
        assert err < TOL, err
    else:
        assert err > 10 * TOL, err


def test_fused_round_verifies_like_the_reference():
    """One fused verify+draft round over a paged latent pool of 2 slots:
    the tokens it emits are those greedy acceptance gives on the
    reference's logits."""
    t, d, tp, dp = _params(seed=7)
    rng = np.random.default_rng(2)
    n, bs, mbs, n_cand = 24, 8, 5, 3
    prompts = rng.integers(1, 128, (2, n)).astype(np.int32)
    tc = init_paged_cache(t, 2, 1 + 2 * mbs, bs, mbs)
    dc = init_cache(d, 2, mbs * bs)
    for i in range(2):
        _, c = M.prefill(tp, t, jnp.asarray(prompts[i:i + 1]),
                         init_cache(t, 1, mbs * bs))
        tc = admit_sequence_paged(t, tc, c, i, jnp.arange(
            1 + i * mbs, 1 + (i + 1) * mbs, dtype=jnp.int32), n, 0)
    t_next = jnp.asarray(rng.integers(1, 128, 2), jnp.int32)
    drafts = jnp.asarray(rng.integers(1, 128, (2, n_cand)), jnp.int32)
    # make slot 0's first draft the reference's choice, so acceptance
    # is exercised past the first token
    first = np.argmax(np.asarray(REF.logits(
        tp, T_DOC, np.append(prompts[0], t_next[0])))[-1])
    drafts = drafts.at[0, 0].set(int(first))
    vout, _ = jax.jit(fused_verify_and_draft, static_argnums=(1, 3, 6))(
        tp, t, dp, d, {"target_cache": tc, "t_next": t_next,
                       "drafts": drafts},
        {"draft_cache": dc, "t_next": t_next}, n_cand)
    ref = np.stack([np.asarray(REF.logits(tp, T_DOC, np.concatenate(
        [prompts[i], [int(t_next[i])], np.asarray(drafts[i])])))[n:]
        for i in range(2)])
    a, nxt, _ = greedy_acceptance(drafts, jnp.asarray(ref))
    assert int(a[0]) >= 1
    np.testing.assert_array_equal(np.asarray(vout["n_emitted"]),
                                  np.asarray(a) + 1)
    np.testing.assert_array_equal(np.asarray(vout["t_next"]),
                                  np.asarray(nxt))


# ---------------------------------------------------------------------------
# (c) the expert share


def test_expert_shares_sum_to_the_uncut_layer():
    """Four shares of 2 experts each of a router over 8 (top-3, sigmoid
    with a correction bias): their held-expert outputs, plus the shared
    expert once, add up to the uncut reference layer."""
    e, k, dm, f = 8, 3, 32, 16
    full = init_moe(jax.random.PRNGKey(0), dm, f, e, "swiglu", jnp.float32,
                    router_experts=e, n_shared=1, score_bias=True)
    full["score_bias"] = 0.5 * jax.random.normal(jax.random.PRNGKey(1),
                                                 (e,))
    x = jax.random.normal(jax.random.PRNGKey(2), (40, dm))
    kw = dict(top_k=k, capacity_factor=float("inf"), activation="swiglu",
              routed_scaling_factor=2.5)
    total = jnp.zeros_like(x)
    for off in range(0, e, 2):
        share = dict(full, **{w: full[w][off:off + 2]
                              for w in ("w_gate", "w_up", "w_down")})
        del share["shared"]
        total = total + _moe_local(share, x, n_experts=2,
                                   expert_offset=off, **kw)
    sh = full["shared"]
    total = total + (jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
                     ) @ sh["w_down"]
    stacked = jax.tree.map(lambda a: a[None], full)
    cfg = dict(top_k=k, n_experts=e, expert_offset=0,
               routed_scaling_factor=2.5)
    want, _ = REF._moe(stacked, 0, x, cfg, None)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=TOL, rtol=TOL)


# ---------------------------------------------------------------------------
# (e) what MLA refuses


def test_tree_speculation_refuses_mla():
    with pytest.raises(ValueError, match="all-attention"):
        ServingEngine(TARGET, DRAFT,
                      config=SchedulerConfig(max_batch=2, spec_tree=(2, 2)))


def test_int8_pools_refuse_mla():
    with pytest.raises(ValueError, match="latent attention"):
        init_paged_cache(TARGET, 2, 9, 8, 4, kv_quant=True)
    with pytest.raises(ValueError, match="latent attention"):
        dataclasses.replace(TARGET, kv_cache_dtype="int8")


# ---------------------------------------------------------------------------
# the configuration


def _bench_doc():
    return json.loads((ROOT / "bench" / "configs" /
                       "kimi-k2-moonlight-v5e-pair.json").read_text())


def test_pair_module_equals_the_benchmark_file():
    doc = _bench_doc()
    t, d = PAIRS["kimi-k2-moonlight-v5e-pair"]
    for cfg, part in ((t, "target"), (d, "draft")):
        assert dataclasses.replace(cfg, source="") == model_config(doc[part])


def test_benchmark_file_keeps_the_published_widths():
    """The catalog keys at the file's top level are Kimi-K2's config as
    run, and agree with the target the program builds; the draft's with
    Moonlight's."""
    doc = _bench_doc()
    t, d = model_config(doc["target"]), model_config(doc["draft"])
    for hf, cfg in ((doc, t), (doc["draft_config"], d)):
        assert hf["hidden_size"] == cfg.d_model
        assert hf["intermediate_size"] == cfg.d_ff
        assert hf["moe_intermediate_size"] == cfg.expert_d_ff
        assert hf["num_attention_heads"] == cfg.n_heads
        assert (hf["q_lora_rank"] or 0) == cfg.q_lora_rank
        assert hf["kv_lora_rank"] == cfg.kv_lora_rank
        assert hf["qk_nope_head_dim"] == cfg.qk_nope_head_dim
        assert hf["qk_rope_head_dim"] == cfg.qk_rope_head_dim
        assert hf["v_head_dim"] == cfg.v_head_dim
        assert hf["num_experts_per_tok"] == cfg.top_k
        assert hf["n_routed_experts"] == cfg.n_experts
        assert hf["n_shared_experts"] == cfg.n_shared_experts
        assert hf["first_k_dense_replace"] == cfg.first_dense_layers
        assert hf["num_hidden_layers"] == cfg.n_layers
        assert hf["vocab_size"] == cfg.vocab_size
        assert hf["rms_norm_eps"] == cfg.norm_eps
        assert hf["routed_scaling_factor"] == cfg.routed_scaling_factor
        assert hf["rope_theta"] == cfg.rope_theta
        assert dict(hf.get("rope_scaling") or {}) == cfg.rope_scaling_dict
    assert t.router_experts == 384 and d.router_experts == 64
    for key, was in doc["published"].items():
        part, name = key.split(".")
        assert getattr(model_config(doc[part]), name) < was


def test_param_counts_match_hand_counts():
    """Hand counts (bf16: 5.58 / 5.01 GB).  Target: attention 101,124,096
    a layer (q 1536-LoRA 29,885,952; kv_a 4,128,768; kv_b 8,388,608; o
    58,720,256; norm 512), norms 14,336; the dense FFN 396,361,728; a MoE
    layer's 8 held + 1 shared experts 396,361,728 and router 2,752,512 +
    384; embedding and head 293,601,280.  Draft: attention 13,763,072,
    norms 4,096, dense 69,206,016, MoE 64 + 2 shared experts 570,949,632
    and router 131,072 + 64, embedding and head 83,886,080."""
    t, d = PAIRS["kimi-k2-moonlight-v5e-pair"]
    attn_t, attn_d = 101_124_096, 13_763_072
    dense_t = attn_t + 14_336 + 396_361_728
    moe_t = attn_t + 14_336 + 396_361_728 + 2_752_512 + 384
    assert t.param_count() == dense_t + 4 * moe_t + 293_601_280
    assert t.param_count() == 2_792_113_664
    dense_d = attn_d + 4_096 + 69_206_016
    moe_d = attn_d + 4_096 + 570_949_632 + 131_072 + 64
    assert d.param_count() == dense_d + 4 * moe_d + 83_886_080
    assert d.param_count() == 2_506_251_008
    assert (t.n_moe_layers, d.n_moe_layers) == (4, 4)
    # per token: all 8 held experts can be chosen; the draft's 6 of 64
    assert t.active_param_count() == t.param_count()
    assert d.active_param_count() == \
        d.param_count() - 4 * 58 * 3 * 2048 * 1408
    tp = jax.eval_shape(lambda k: M.init_params(t, k), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(tp)) == \
        t.param_count() + t.d_model          # + the final norm


def test_yarn_frequencies_and_scale():
    """Kimi-K2's YaRN (factor 32 over 4096, beta_fast = beta_slow = 1):
    the first 20 rotary frequencies are theta's, the last 12 are divided
    by 32; the softmax scale is 192 ** -0.5 * (0.1 ln 32 + 1) ** 2."""
    t, d = PAIRS["kimi-k2-moonlight-v5e-pair"]
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    f = mla_lib.rope_inv_freq(t)
    np.testing.assert_allclose(f[:20], plain[:20], rtol=1e-6)
    np.testing.assert_allclose(f[20:], plain[20:] / 32, rtol=1e-6)
    np.testing.assert_allclose(mla_lib.rope_inv_freq(d), plain, rtol=1e-6)
    assert mla_lib.softmax_scale(t) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    assert mla_lib.softmax_scale(d) == pytest.approx(192 ** -0.5)
    assert isinstance(hash(t), int)
    assert ModelConfig(**{**dataclasses.asdict(t),
                          "rope_scaling": t.rope_scaling_dict}) == t


def test_engine_exports_the_expert_cut():
    eng = ServingEngine(TARGET, DRAFT, config=SchedulerConfig(max_batch=2))
    g = eng.metrics()["metrics"]["gauges"]
    assert g["moe_experts_held"] == {'{model="target"}': 4.0,
                                     '{model="draft"}': 8.0}
    assert g["moe_router_experts"] == {'{model="target"}': 8.0,
                                       '{model="draft"}': 8.0}
