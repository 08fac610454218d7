"""Speculative decoding: draft-then-verify with batched per-sequence
acceptance, plus the paper's acceptance model (Appendix A.1).

Round protocol (uniform shapes — no per-sequence catch-up feeds)
----------------------------------------------------------------
Invariant: both caches hold positions [0, P); ``t_next`` (B,) is the last
committed token, not yet fed to either model.

1. **Draft** feeds ``n_cand + 1`` tokens one step at a time:
   ``x_0 = t_next``, ``x_i = d_i`` (its own greedy/sampled prediction),
   producing drafts ``d_1..d_m`` (m = n_cand).  The final feed of ``d_m``
   produces no draft but commits it, so a fully-accepted round needs no
   catch-up next round.  Per-step pendings are kept for rollback.
2. **Target** verifies ``[t_next, d_1..d_m]`` in one forward (m+1 positions),
   yielding greedy predictions ``g_0..g_m``.
3. **Accept** ``a = |longest prefix with d_{i+1} == g_i|``; commit ``a+1``
   input tokens on the target, roll the draft back to ``a+1`` kept inputs,
   and emit ``a+1`` new tokens (``d_1..d_a`` plus bonus ``g_a``).  This
   matches the paper: 1..n_cand+1 tokens per round, E[n] per Eq. (12).

Losslessness: with greedy acceptance the emitted stream equals the target
model's own greedy decoding, token for token (tested in
``tests/test_spec_decode.py``).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, ModelConfig
from repro.models import model as M

# ---------------------------------------------------------------------------
# the paper's acceptance model (Appendix A.1, Eqs. 10-12)


def acceptance_pmf(p: float, n_cand: int) -> jnp.ndarray:
    """P[n_generated = k] for k = 1..n_cand+1 under i.i.d. acceptance p."""
    ks = jnp.arange(1, n_cand + 2)
    pmf = p ** (ks - 1) * (1 - p)
    pmf = pmf.at[-1].set(p ** n_cand)
    return pmf


def expected_generated(p: float, n_cand: int) -> float:
    """E[n_generated] under the paper's acceptance pmf (Eqs. 10-11).

    ERRATUM: the paper's closed form (Eq. 12) is algebraically inconsistent
    with its own pmf — summing k * P[k] over Eqs. (10)-(11) gives the
    truncated-geometric mean ``(1 - p^{n+1}) / (1 - p)`` (Monte-Carlo
    verified in tests/test_spec_decode.py; this also matches Leviathan et
    al. 2023 Eq. 1).  We implement the correct sum.
    """
    if p >= 1.0:
        return float(n_cand + 1)
    return float((1.0 - p ** (n_cand + 1)) / (1.0 - p))


def expected_generated_paper_eq12(p: float, n_cand: int) -> float:
    """The paper's Eq. (12) as printed — kept for the erratum comparison."""
    if p >= 1.0:
        return float(n_cand + 1)
    return float((n_cand * p ** (n_cand + 2)
                  - (n_cand + 1) * p ** (n_cand + 1) + 1) / (1 - p))


def record_acceptance(metrics, n_accept, n_cand: int, live_mask=None,
                      n_draft: int | None = None, mode: str = "chain"):
    """Observe one verified round's per-sequence accepted-draft counts
    into the registry's acceptance histogram (host-side — call with the
    materialized ``RoundOutput.n_accept``, never inside jit).

    ``live_mask`` drops slots holding retired/dummy sequences so the
    histogram reflects real requests only.  The histogram's integer
    buckets 0..n_cand make the paper's acceptance-rate estimate exact:
    ``sum / (count * n_cand)`` is the measured per-round acceptance.
    For trees pass ``n_cand`` = tree depth (the max accepted path length).

    ``n_draft`` is the number of candidate tokens *verified* per sequence
    per round (chain: n_cand; tree: n_nodes - 1).  It feeds the waste
    counters that make chain vs tree efficiency directly comparable:

    * ``spec_tokens_accepted_total{mode=}`` / ``spec_tokens_wasted_total``
      — candidate tokens the target pass kept / threw away;
    * ``spec_verify_rounds_total`` — per-sequence verified rounds (the
      denominator: accepted/rounds + 1 = emitted tokens per target pass);
    * ``spec_accept_depth_total{depth=d}`` — rounds whose accepted path
      reached at least depth d (per-depth acceptance histogram).
    """
    if not metrics.enabled:
        return
    import numpy as _np
    from repro.obs.metrics import acceptance_buckets
    hist = metrics.histogram(
        "spec_accepted_tokens",
        "accepted draft tokens per sequence per verified round",
        buckets=acceptance_buckets(n_cand))
    arr = _np.asarray(n_accept)
    if live_mask is not None:
        arr = arr[_np.asarray(live_mask)]
    for v in arr.tolist():
        hist.observe(float(v))

    n_draft = n_cand if n_draft is None else n_draft
    accepted = metrics.counter(
        "spec_tokens_accepted_total",
        "draft candidate tokens accepted by target verification")
    wasted = metrics.counter(
        "spec_tokens_wasted_total",
        "draft candidate tokens verified by the target but rejected")
    rounds = metrics.counter(
        "spec_verify_rounds_total",
        "per-sequence verified speculation rounds")
    depth_c = metrics.counter(
        "spec_accept_depth_total",
        "rounds whose accepted path reached at least this depth")
    accepted.inc(float(arr.sum()), mode=mode)
    wasted.inc(float((n_draft - arr).sum()), mode=mode)
    rounds.inc(float(arr.size), mode=mode)
    for d in range(1, n_cand + 1):
        depth_c.inc(float((arr >= d).sum()), mode=mode, depth=str(d))


# ---------------------------------------------------------------------------
# acceptance rules


def greedy_acceptance(drafts: jax.Array, target_logits: jax.Array):
    """Greedy (lossless) acceptance.

    drafts (B, m); target_logits (B, m+1, V) for inputs [t_next, d_1..d_m].
    Returns (n_accept (B,) in [0,m], next_token (B,), n_commit (B,) = a+1).
    """
    g = jnp.argmax(target_logits, axis=-1).astype(drafts.dtype)  # (B, m+1)
    m = drafts.shape[1]
    match = drafts == g[:, :m]                                   # d_{i+1}==g_i
    prefix = jnp.cumprod(match.astype(jnp.int32), axis=1)
    a = prefix.sum(axis=1)                                       # (B,)
    next_token = jnp.take_along_axis(g, a[:, None], axis=1)[:, 0]
    return a, next_token, a + 1


def sampled_acceptance(drafts: jax.Array, draft_logits: jax.Array,
                       target_logits: jax.Array, key,
                       temperature: float = 1.0):
    """Leviathan et al. (2023) lossless *sampling* acceptance.

    Accept d_i with prob min(1, p_t(d_i)/p_d(d_i)); on first rejection,
    resample from max(0, p_t - p_d) normalized.  Returns
    (n_accept, next_token, n_commit).
    """
    b, m = drafts.shape
    pt = jax.nn.softmax(target_logits[:, :m] / temperature, axis=-1)
    pd = jax.nn.softmax(draft_logits / temperature, axis=-1)
    di = drafts[..., None]
    pt_d = jnp.take_along_axis(pt, di, axis=-1)[..., 0]
    pd_d = jnp.take_along_axis(pd, di, axis=-1)[..., 0]
    k_acc, k_res, k_bonus = jax.random.split(key, 3)
    u = jax.random.uniform(k_acc, (b, m))
    accept = u < jnp.minimum(1.0, pt_d / jnp.maximum(pd_d, 1e-20))
    prefix = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    a = prefix.sum(axis=1)

    # residual distribution at the first rejected position
    idx = jnp.minimum(a, m - 1)
    pt_rej = jnp.take_along_axis(pt, idx[:, None, None], axis=1)[:, 0]
    pd_rej = jnp.take_along_axis(pd, idx[:, None, None], axis=1)[:, 0]
    residual = jnp.maximum(pt_rej - pd_rej, 0.0)
    residual = residual / jnp.maximum(residual.sum(-1, keepdims=True), 1e-20)
    resampled = jax.random.categorical(k_res, jnp.log(residual + 1e-20))

    # fully-accepted rows sample the bonus position from the target
    bonus_logits = target_logits[:, m] / temperature
    bonus = jax.random.categorical(k_bonus, bonus_logits)
    next_token = jnp.where(a == m, bonus, resampled).astype(drafts.dtype)
    return a, next_token, a + 1


# ---------------------------------------------------------------------------
# draft generation with rollback support


def draft_generate(params, cfg: ModelConfig, cache, t_next: jax.Array,
                   n_cand: int, mesh=None):
    """Generate ``n_cand`` greedy draft tokens, feeding n_cand+1 inputs.

    Returns (drafts (B, m), draft_logits (B, m, V), cache, step_pendings).
    The cache has all n_cand+1 inputs written (pos advanced); roll back with
    :func:`rollback_draft`.
    """
    b = t_next.shape[0]
    tok = t_next[:, None]
    drafts, dlogits, step_pendings = [], [], []
    for i in range(n_cand + 1):
        logits, cache, pend = M.decode(params, cfg, cache, tok, mesh)
        cache = {"layers": cache["layers"], "pos": cache["pos"] + 1}
        step_pendings.append(pend)
        if i < n_cand:
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(tok.dtype)[:, None]
            drafts.append(tok[:, 0])
            dlogits.append(logits[:, 0])
    return (jnp.stack(drafts, axis=1), jnp.stack(dlogits, axis=1), cache,
            step_pendings)


def rollback_draft(cfg: ModelConfig, cache, step_pendings, n_keep):
    """Rewind the draft cache to keep only the first ``n_keep`` (B,) of the
    ``len(step_pendings)`` single-token steps written by draft_generate."""
    m = len(step_pendings)
    nk = jnp.asarray(n_keep, jnp.int32)
    pos0 = cache["pos"] - m
    new_layers = list(cache["layers"])
    for li, kind in enumerate(cfg.layer_pattern):
        if kind in ("attn", "mla"):
            continue  # full cache: stale rows beyond pos are invisible
        if kind == "swa":
            for i, pend in enumerate(step_pendings):
                saved = pend[li]["saved"]
                if not saved:
                    continue
                keep_i = (i < nk).astype(jnp.int32)
                fix = jax.vmap(
                    lambda cc, sv, p=pos0 + i, k=keep_i:
                    _restore_step(cc, sv, p, k, cfg.sliding_window))
                new_layers[li] = fix(new_layers[li],
                                     jax.tree.map(lambda x: x, saved))
        else:  # recurrent: pick the state after n_keep steps
            # stacks: step i holds [state_after_i, state_after_i+1]
            stacks = [p[li]["stack"] for p in step_pendings]
            first = stacks[0]
            posts = [jax.tree.map(lambda s: s[:, :, 1], st) for st in stacks]
            pre = jax.tree.map(lambda s: s[:, :, 0], first)
            seq = jax.tree.map(
                lambda p0, *ps: jnp.concatenate(
                    [p0[:, :, None]] + [x[:, :, None] for x in ps], axis=2),
                pre, *posts)  # (G, B, m+1, ...)
            sel = _select_stacked(cfg, kind)
            new_layers[li] = jax.vmap(lambda st: sel(st, nk))(seq)
    return {"layers": tuple(new_layers), "pos": pos0 + nk}


def _restore_step(cache_kv, saved, pos, keep, window):
    from repro.models.attention import restore_rejected_rows
    return restore_rejected_rows(cache_kv, saved, pos, keep, window)


def _select_stacked(cfg, kind):
    from repro.models import rglru as rglru_lib
    from repro.models import rwkv as rwkv_lib
    return (rglru_lib.select_rglru_state if kind == "rglru"
            else rwkv_lib.select_rwkv_state)


# ---------------------------------------------------------------------------
# one full speculative round (jit-friendly)


def spec_round(target_params, target_cfg: ModelConfig, target_cache,
               draft_params, draft_cfg: ModelConfig, draft_cache,
               t_next: jax.Array, n_cand: int, mesh=None, key=None,
               sample: bool = False):
    """One draft-then-verify round for one batch.

    Returns dict with: tokens (B, m+1) — the m+1 candidate output slots
    (d_1..d_m, bonus); n_emitted (B,) in [1, m+1] — how many of them are
    valid; t_next (B,); updated caches.
    """
    drafts, dlogits, draft_cache, pendings = draft_generate(
        draft_params, draft_cfg, draft_cache, t_next, n_cand, mesh)

    verify_in = jnp.concatenate([t_next[:, None], drafts], axis=1)
    tlogits, target_cache, tpend = M.decode(
        target_params, target_cfg, target_cache, verify_in, mesh)

    if sample:
        a, nxt, n_commit = sampled_acceptance(drafts, dlogits, tlogits, key)
    else:
        a, nxt, n_commit = greedy_acceptance(drafts, tlogits)

    target_cache = M.commit(target_cfg, target_cache, tpend, n_commit,
                            n_cand + 1)
    draft_cache = rollback_draft(draft_cfg, draft_cache, pendings, n_commit)

    out, n_emitted = chain_output_slots(drafts, a, nxt)
    return {"tokens": out, "n_emitted": n_emitted, "t_next": nxt,
            "target_cache": target_cache, "draft_cache": draft_cache,
            "n_accept": a}


def chain_output_slots(drafts: jax.Array, a: jax.Array, nxt: jax.Array):
    """A chain round's output slots: the ``a`` accepted drafts, then the
    bonus token ``nxt`` at slot ``a``, zeros after.  Returns ``(tokens
    (B, m+1), n_emitted (B,) = a + 1)``."""
    m = drafts.shape[1]
    out = jnp.where(jnp.arange(m)[None, :] < a[:, None], drafts, 0)
    out = jnp.concatenate([out, jnp.zeros_like(a[:, None])], axis=1)
    out = jax.vmap(lambda row, i, t: row.at[i].set(t))(out, a, nxt)
    return out, a + 1


# ---------------------------------------------------------------------------
# speculation trees (SpecExec-style): top-k branching per depth, verified
# in one masked target pass
#
# Layout: the tree is flattened breadth-first into a candidate buffer of
# ``n_nodes`` tokens.  Node 0 is the *root* — the last committed token
# ``t_next`` (depth 0, input only).  Level d holds prod(branching[:d])
# nodes: every level-(d-1) node gets the draft's top-``branching[d-1]``
# continuations as children.  Cache rows for the buffer are written at
# *slots* ``[pos, pos + n_nodes)`` in BFS order, while each node's RoPE
# position is the *logical* ``pos + depth`` (siblings are alternatives for
# the same step, so they share a position but occupy distinct slots).
# Attention inside the buffer follows the ancestor-or-self mask; committed
# rows ``< pos`` stay fully visible.  After verification the deepest
# accepted root-to-leaf path is compacted back to contiguous slots
# (:func:`tree_commit_cache`) so the committed prefix never fragments.

#: ancestor sets are packed into int32 bitmasks for the Pallas kernels
MAX_TREE_NODES = 31


@lru_cache(maxsize=None)
def tree_layout(branching: tuple) -> dict:
    """Static BFS layout for a ``branching`` = (k_1, .., k_D) tree.

    Returns numpy arrays (constants under jit): ``n_nodes``, ``depth``
    (n,), ``parent`` (n,) with parent[0] = 0, ``level_sizes`` /
    ``level_offsets`` (D+1,), ``first_child`` (n,) (-1 for leaves),
    ``anc_mask`` (n, n) bool ancestor-or-self, and ``anc_bits`` (n,)
    int32 with bit j set iff node j is an ancestor-or-self of node i.
    """
    branching = tuple(int(k) for k in branching)
    if not branching or any(k < 1 for k in branching):
        raise ValueError(f"branching factors must be >= 1: {branching}")
    level_sizes = [1]
    for k in branching:
        level_sizes.append(level_sizes[-1] * k)
    n = sum(level_sizes)
    if n > MAX_TREE_NODES:
        raise ValueError(f"tree {branching} has {n} nodes; int32 ancestor "
                         f"bitmasks cap the buffer at {MAX_TREE_NODES}")
    offsets = np.concatenate([[0], np.cumsum(level_sizes)[:-1]])
    depth = np.zeros(n, np.int32)
    parent = np.zeros(n, np.int32)
    for d in range(1, len(level_sizes)):
        off, cnt = offsets[d], level_sizes[d]
        depth[off:off + cnt] = d
        parent[off:off + cnt] = offsets[d - 1] + (np.arange(cnt)
                                                  // branching[d - 1])
    first_child = np.full(n, -1, np.int32)
    for d in range(len(branching)):
        off, cnt = offsets[d], level_sizes[d]
        first_child[off:off + cnt] = offsets[d + 1] + (np.arange(cnt)
                                                       * branching[d])
    anc = np.eye(n, dtype=bool)
    for i in range(1, n):
        anc[i] |= anc[parent[i]]
    bits = (anc.astype(np.int64) << np.arange(n)[None, :]).sum(1)
    return {"n_nodes": n, "branching": branching,
            "depth": depth, "parent": parent,
            "level_sizes": np.asarray(level_sizes, np.int32),
            "level_offsets": np.asarray(offsets, np.int32),
            "first_child": first_child,
            "anc_mask": anc, "anc_bits": bits.astype(np.int32)}


def tree_n_nodes(branching) -> int:
    """Buffer size (root + all candidates) of a ``branching`` tree."""
    return int(tree_layout(tuple(branching))["n_nodes"])


def tree_supported(cfg: ModelConfig) -> bool:
    """Tree speculation needs every layer to see the full prefix (the
    ancestor mask subsets full causal attention): all-ATTN decoder-only
    configs.  SWA rings, recurrent state, and cross-attention carry
    order-dependent state that a branched buffer cannot share; latent
    attention (MLA) has no tree-masked path."""
    return (not cfg.encoder_decoder
            and all(kind == ATTN for kind in cfg.layer_pattern))


def tree_spec(branching: tuple, level: int | None = None) -> dict:
    """The ``spec_tree`` attention descriptor (static numpy constants).

    ``level=None``: verify the whole buffer at once (``prev=0``).
    ``level=d``: the draft's feed of level ``d``'s nodes after ``prev``
    buffer rows are already written.  Keys: ``depths`` (Sq,) node depths,
    ``prev`` rows of the buffer already in cache, ``mask`` (Sq, prev+Sq)
    ancestor-or-self visibility over the buffer written so far, and (full
    buffer only) ``anc_bits`` for the Pallas tree kernels.
    """
    lay = tree_layout(tuple(branching))
    if level is None:
        return {"depths": lay["depth"], "prev": 0, "mask": lay["anc_mask"],
                "anc_bits": lay["anc_bits"]}
    off = int(lay["level_offsets"][level])
    cnt = int(lay["level_sizes"][level])
    return {"depths": lay["depth"][off:off + cnt], "prev": off,
            "mask": lay["anc_mask"][off:off + cnt, :off + cnt]}


# ---------------------------------------------------------------------------
# tree-shaped acceptance model (planner objective; satellite of Eq. 12)


def acceptance_pmf_tree(p: float, branching: tuple) -> jnp.ndarray:
    """P[n_generated = d+1] for d = 0..D on a ``branching`` tree.

    Per-level coverage under i.i.d. acceptance p: the accepted node at
    depth d-1 has k_d children, each independently acceptable with prob
    p, so the path extends with ``q_d = 1 - (1-p)^{k_d}`` (any child
    matches).  The emitted count is path length + 1 (bonus token).
    """
    branching = tuple(branching)
    qs = [1.0 - (1.0 - p) ** k for k in branching]
    pmf, run = [], 1.0
    for q in qs:
        pmf.append(run * (1.0 - q))
        run *= q
    pmf.append(run)
    return jnp.asarray(pmf)


def expected_generated_tree(p: float, branching: tuple) -> float:
    """E[n_generated] for a tree: ``1 + sum_d prod_{j<=d} q_j`` — the tree
    analogue of :func:`expected_generated` (chain = all k_j = 1)."""
    if p >= 1.0:
        return float(len(tuple(branching)) + 1)
    e, run = 1.0, 1.0
    for k in tuple(branching):
        run *= 1.0 - (1.0 - p) ** k
        e += run
    return float(e)


# ---------------------------------------------------------------------------
# tree acceptance rules


def tree_greedy_acceptance(tokens: jax.Array, target_logits: jax.Array,
                           branching: tuple):
    """Greedy (lossless) acceptance over a verified tree buffer.

    ``tokens`` (B, N) is the BFS buffer (root = committed ``t_next`` at
    column 0); ``target_logits`` (B, N, V) are the target's logits at
    every node.  A node is *accepted* iff its token equals the target's
    greedy prediction at its parent AND its parent is accepted — so the
    accepted set is exactly the target's own greedy path through the
    tree (top-k children are distinct, hence at most one child per level
    matches the unique argmax).

    Returns ``(n_accept (B,), next_token (B,), out_tokens (B, D+1),
    path_idx (B, D+1))`` where ``path_idx[:, d]`` is the buffer index of
    the accepted depth-d node (0 = root beyond the path) for
    :func:`tree_commit_cache`.
    """
    lay = tree_layout(tuple(branching))
    depth_cap = len(lay["level_sizes"]) - 1
    b = tokens.shape[0]
    g = jnp.argmax(target_logits, axis=-1).astype(tokens.dtype)   # (B, N)

    acc_levels = [jnp.ones((b, 1), bool)]                         # root
    for d in range(1, depth_cap + 1):
        off = int(lay["level_offsets"][d])
        cnt = int(lay["level_sizes"][d])
        par = lay["parent"][off:off + cnt]
        match = tokens[:, off:off + cnt] == g[:, par]
        par_local = par - int(lay["level_offsets"][d - 1])
        acc_levels.append(match & acc_levels[d - 1][:, par_local])

    n_accept = sum(lvl.any(axis=1).astype(jnp.int32)
                   for lvl in acc_levels[1:])                     # (B,)
    path_cols = [jnp.zeros((b,), jnp.int32)]
    out_cols = []
    for d in range(1, depth_cap + 1):
        off = int(lay["level_offsets"][d])
        cnt = int(lay["level_sizes"][d])
        lvl = acc_levels[d].astype(jnp.int32)                     # <=1 hot
        idx = jnp.arange(off, off + cnt, dtype=jnp.int32)
        path_cols.append((lvl * idx[None, :]).sum(axis=1))
        out_cols.append((lvl.astype(tokens.dtype)
                         * tokens[:, off:off + cnt]).sum(axis=1))
    path_idx = jnp.stack(path_cols, axis=1)                       # (B, D+1)
    best = jnp.take_along_axis(path_idx, n_accept[:, None], axis=1)[:, 0]
    nxt = jnp.take_along_axis(g, best[:, None], axis=1)[:, 0]
    out = jnp.stack(out_cols + [jnp.zeros((b,), tokens.dtype)], axis=1)
    out = jax.vmap(lambda row, i, t: row.at[i].set(t))(out, n_accept, nxt)
    return n_accept, nxt, out, path_idx


def tree_sampled_acceptance(tokens: jax.Array, draft_logits: jax.Array,
                            target_logits: jax.Array, branching: tuple,
                            key, temperature: float = 1.0):
    """SpecInfer-style multi-candidate rejection sampling down the tree.

    At the current accepted node, try its k children in draft-rank order:
    accept child c with prob ``min(1, res(c) / p_d(c))`` where ``res``
    starts as the target distribution; on rejection subtract the draft
    proposal mass and renormalize both (sampling-without-replacement
    correction), and if every child is rejected emit a token from the
    residual.  Greedy mode is the losslessness-tested path; this sampled
    walk is distribution-sanity-tested in tests/test_tree_spec.py.

    Same return signature as :func:`tree_greedy_acceptance`.
    """
    lay = tree_layout(tuple(branching))
    branching = lay["branching"]
    b, _, v = target_logits.shape
    pt_all = jax.nn.softmax(target_logits / temperature, axis=-1)
    pd_all = jax.nn.softmax(draft_logits / temperature, axis=-1)
    keys = jax.random.split(key, sum(branching) + len(branching) + 1)
    ki = 0

    cur = jnp.zeros((b,), jnp.int32)          # deepest accepted node
    alive = jnp.ones((b,), bool)              # path still extending
    n_accept = jnp.zeros((b,), jnp.int32)
    nxt = jnp.zeros((b,), tokens.dtype)
    path_cols = [cur]
    out_cols = []
    fc_arr = jnp.asarray(lay["first_child"])
    for d, k_d in enumerate(branching):
        fc = fc_arr[cur]                                          # (B,)
        res = jnp.take_along_axis(pt_all, cur[:, None, None], 1)[:, 0]
        pdm = jnp.take_along_axis(pd_all, cur[:, None, None], 1)[:, 0]
        accepted = jnp.zeros((b,), bool)
        child_tok = jnp.zeros((b,), tokens.dtype)
        child_idx = cur
        for j in range(k_d):
            cidx = fc + j
            ctok = jnp.take_along_axis(tokens, cidx[:, None], 1)[:, 0]
            ci = ctok[:, None].astype(jnp.int32)
            p_res = jnp.take_along_axis(res, ci, 1)[:, 0]
            p_d = jnp.take_along_axis(pdm, ci, 1)[:, 0]
            u = jax.random.uniform(keys[ki], (b,))
            ki += 1
            acc_j = (alive & ~accepted
                     & (u < jnp.minimum(1.0, p_res
                                        / jnp.maximum(p_d, 1e-20))))
            child_tok = jnp.where(acc_j, ctok, child_tok)
            child_idx = jnp.where(acc_j, cidx, child_idx)
            accepted |= acc_j
            rej = alive & ~accepted
            res_new = jnp.maximum(res - pdm, 0.0)
            res_new = res_new / jnp.maximum(
                res_new.sum(-1, keepdims=True), 1e-20)
            res = jnp.where(rej[:, None], res_new, res)
            pdm_new = pdm * (1.0 - jax.nn.one_hot(ctok, v, dtype=pdm.dtype))
            pdm_new = pdm_new / jnp.maximum(
                pdm_new.sum(-1, keepdims=True), 1e-20)
            pdm = jnp.where(rej[:, None], pdm_new, pdm)
        failed = alive & ~accepted
        bonus = jax.random.categorical(keys[ki], jnp.log(res + 1e-20))
        ki += 1
        nxt = jnp.where(failed, bonus.astype(tokens.dtype), nxt)
        n_accept += accepted.astype(jnp.int32)
        alive &= accepted
        out_cols.append(jnp.where(accepted, child_tok, 0))
        cur = jnp.where(accepted, child_idx, cur)
        path_cols.append(jnp.where(accepted, child_idx, 0))
    pt_deep = jnp.take_along_axis(pt_all, cur[:, None, None], 1)[:, 0]
    bonus = jax.random.categorical(keys[ki], jnp.log(pt_deep + 1e-20))
    nxt = jnp.where(alive, bonus.astype(tokens.dtype), nxt)
    out = jnp.stack(out_cols + [jnp.zeros((b,), tokens.dtype)], axis=1)
    out = jax.vmap(lambda row, i, t: row.at[i].set(t))(out, n_accept, nxt)
    return n_accept, nxt, out, jnp.stack(path_cols, axis=1)


# ---------------------------------------------------------------------------
# tree draft generation + accepted-path commit


def draft_tree_generate(params, cfg: ModelConfig, cache, t_next: jax.Array,
                        branching: tuple, mesh=None,
                        collect_logits: bool = False):
    """Expand the draft's top-k speculation tree level by level.

    Feeds the root (``t_next``) then each level's nodes in one masked
    decode step per depth; every level-(d-1) node contributes its
    top-``branching[d-1]`` continuations.  All ``n_nodes`` buffer rows
    end up written to the cache (slots ``[pos, pos + n_nodes)``), so a
    fully-accepted round needs no catch-up feed — mirroring the chain's
    n_cand+1 protocol.  Returns ``(tok_buf (B, N), draft_logits
    (B, N, V) | None, cache)`` with ``pos`` advanced by ``n_nodes``.
    """
    lay = tree_layout(tuple(branching))
    branching = lay["branching"]
    b = t_next.shape[0]
    feed = t_next[:, None].astype(jnp.int32)
    toks, dlogits = [feed], []
    for d in range(len(branching) + 1):
        spec = tree_spec(branching, level=d)
        logits, cache, _ = M.decode(params, cfg, cache, feed, mesh,
                                    spec_tree=spec)
        cache = dict(cache, pos=cache["pos"] + feed.shape[1])
        if collect_logits:
            dlogits.append(logits)
        if d < len(branching):
            _, topk = jax.lax.top_k(logits, branching[d])  # (B, w, k)
            feed = topk.reshape(b, -1).astype(jnp.int32)
            toks.append(feed)
    tok_buf = jnp.concatenate(toks, axis=1)
    logits_buf = (jnp.concatenate(dlogits, axis=1) if collect_logits
                  else None)
    return tok_buf, logits_buf, cache


def tree_commit_cache(cfg: ModelConfig, cache, path_idx: jax.Array,
                      n_keep, branching: tuple, pos_offset: int = 0):
    """Commit a verified tree's accepted root path by *compaction*: the
    accepted buffer rows (root + path) are gathered from their scattered
    BFS slots and scattered back contiguously at the frontier, then
    ``pos`` advances past the kept rows.  Rows beyond the new ``pos``
    are stale-but-invisible (the standing decode invariant) and get
    overwritten by the next round's buffer.

    ``path_idx`` (B, D+1) comes from the acceptance rule; ``n_keep``
    (B,) is the accepted path length ``a`` (``a + 1`` rows kept).
    ``pos_offset`` is how far ``cache['pos']`` already advanced past the
    buffer start (0 for the target, whose decode does not move ``pos``;
    ``n_nodes`` for the draft after :func:`draft_tree_generate`).
    Supports contiguous and paged (block-table) ATTN caches.
    """
    from repro.models.attention import paged_row_indices
    dplus = path_idx.shape[1]
    nk = jnp.asarray(n_keep, jnp.int32)
    base = cache["pos"] - pos_offset                       # (B,)
    src = base[:, None] + path_idx                         # (B, D+1)
    dst = base[:, None] + jnp.arange(dplus, dtype=jnp.int32)[None, :]
    paged = "block_tables" in cache

    def fix_pool(p, srows, drows):
        nb, bs = p.shape[1], p.shape[2]
        flat = p.reshape((p.shape[0], nb * bs) + p.shape[3:])
        rows = flat[:, srows.reshape(-1)]
        flat = flat.at[:, drows.reshape(-1)].set(rows)
        return flat.reshape(p.shape)

    def fix_buf(buf):
        def one(rowbuf, s_b, d_b):     # (S, ...) per (group, batch)
            rows = jnp.take(rowbuf, s_b, axis=0, mode="clip")
            return rowbuf.at[d_b].set(rows, mode="drop")
        return jax.vmap(lambda bg: jax.vmap(one)(bg, src, dst))(buf)

    new_layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        if kind != ATTN:
            raise ValueError("tree_commit_cache requires an all-attention "
                             f"layer pattern (layer {i} is {kind!r})")
        leaf = cache["layers"][i]
        if paged:
            bs_blk = leaf["k"].shape[2]
            srows = paged_row_indices(cache["block_tables"], src, bs_blk)
            drows = paged_row_indices(cache["block_tables"], dst, bs_blk)
            new_layers.append({kk: fix_pool(vv, srows, drows)
                               for kk, vv in leaf.items()})
        else:
            new_layers.append({kk: fix_buf(vv) for kk, vv in leaf.items()})
    out = dict(cache, layers=tuple(new_layers), pos=base + nk + 1)
    return out


# ---------------------------------------------------------------------------
# one full tree-speculation round (jit-friendly; mirrors spec_round)


def spec_round_tree(target_params, target_cfg: ModelConfig, target_cache,
                    draft_params, draft_cfg: ModelConfig, draft_cache,
                    t_next: jax.Array, branching: tuple, mesh=None,
                    key=None, sample: bool = False):
    """One draft-tree-then-verify round for one batch.

    Same contract as :func:`spec_round` with ``tokens`` (B, D+1): the
    accepted path's tokens then the bonus token at slot ``a``.
    """
    branching = tuple(branching)
    n_nodes = tree_n_nodes(branching)
    tok_buf, dlogits, draft_cache = draft_tree_generate(
        draft_params, draft_cfg, draft_cache, t_next, branching, mesh,
        collect_logits=sample)

    tlogits, target_cache, _ = M.decode(
        target_params, target_cfg, target_cache, tok_buf, mesh,
        spec_tree=tree_spec(branching))

    if sample:
        a, nxt, out, path_idx = tree_sampled_acceptance(
            tok_buf, dlogits, tlogits, branching, key)
    else:
        a, nxt, out, path_idx = tree_greedy_acceptance(tok_buf, tlogits,
                                                       branching)

    target_cache = tree_commit_cache(target_cfg, target_cache, path_idx,
                                     a, branching)
    draft_cache = tree_commit_cache(draft_cfg, draft_cache, path_idx,
                                    a, branching, pos_offset=n_nodes)
    return {"tokens": out, "n_emitted": a + 1, "t_next": nxt,
            "target_cache": target_cache, "draft_cache": draft_cache,
            "n_accept": a}
