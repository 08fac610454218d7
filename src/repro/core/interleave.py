"""Interleaved Batch Pipeline (paper §4.1): dual-batch rotation.

The paper runs two batches in anti-phase: in slot t_n the *target* verifies
batch 1 while the *draft* generates candidates for batch 0; the roles swap
in t_{n+1}.  On GPU this needs two processes + shared memory (paper App.
A.2); in JAX the same concurrency is expressed as ONE fused jit step that
contains both computations, which XLA may schedule side by side.  The
serving path keeps the target device-resident, so there are no
streamed-weight copies in the step yet for the draft to hide.

This module holds the per-batch state (:class:`BatchState`), the host-side
result of a round (:class:`RoundOutput`) and the two fused programs, chain
(:func:`fused_verify_and_draft`) and tree
(:func:`fused_tree_verify_and_draft`).  The round runner that jits and
dispatches them is :class:`repro.core.pipeline.SpecOffloadEngine`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.spec_decode import (chain_output_slots, draft_generate,
                                    draft_tree_generate, greedy_acceptance,
                                    tree_commit_cache, tree_greedy_acceptance,
                                    tree_n_nodes, tree_spec)
from repro.models import model as M


@dataclass
class BatchState:
    """Per-interleaved-batch decoding state."""
    target_cache: dict
    draft_cache: dict
    t_next: jax.Array            # (B,) last committed token (not yet fed)
    drafts: jax.Array | None     # (B, m) candidates awaiting verification
    draft_pendings: list | None  # rollback info for the draft steps
    emitted: list                # python-side: list of (tokens, n_emitted)


@dataclass
class RoundOutput:
    """Host-side result of one verified rotation round (one batch)."""
    tokens: np.ndarray           # (B, m+1) output slots (d_1..d_a, bonus, 0s)
    n_emitted: np.ndarray        # (B,) in [1, m+1]: valid prefix of tokens
    n_accept: np.ndarray         # (B,) accepted draft tokens this round
    # perf_counter stamps of the round, taken unconditionally so request
    # timelines and the engine's phase counters work without the span
    # tracer: [t0, t_dispatched) dispatches the fused and rollback
    # programs, [t_dispatched, t1) waits for and fetches the outputs
    t0: float = 0.0
    t_dispatched: float = 0.0
    t1: float = 0.0


def fused_verify_and_draft(target_params, target_cfg: ModelConfig,
                           draft_params, draft_cfg: ModelConfig,
                           verify_state: dict, draft_state: dict,
                           n_cand: int, mesh=None):
    """The fused step: target verifies batch V's drafts while the draft
    model generates candidates for batch D — one XLA program.

    verify_state: {target_cache, t_next, drafts}
    draft_state:  {draft_cache, t_next}
    Returns (verify_out, draft_out) where verify_out carries acceptance
    results and draft_out carries new candidates.
    """
    # --- target side: verify batch V
    v_in = jnp.concatenate([verify_state["t_next"][:, None],
                            verify_state["drafts"]], axis=1)
    tlogits, tcache, tpend = M.decode(
        target_params, target_cfg, verify_state["target_cache"], v_in, mesh)
    a, nxt, n_commit = greedy_acceptance(verify_state["drafts"], tlogits)
    tcache = M.commit(target_cfg, tcache, tpend, n_commit, n_cand + 1)

    # --- draft side: generate for batch D (independent compute, same program)
    drafts, dlogits, dcache, dpend = draft_generate(
        draft_params, draft_cfg, draft_state["draft_cache"],
        draft_state["t_next"], n_cand, mesh)

    out, n_emitted = chain_output_slots(verify_state["drafts"], a, nxt)
    verify_out = {"target_cache": tcache, "tokens": out,
                  "n_emitted": n_emitted, "t_next": nxt, "n_accept": a}
    draft_out = {"drafts": drafts, "draft_cache": dcache,
                 "pendings": dpend}
    return verify_out, draft_out


def fused_tree_verify_and_draft(target_params, target_cfg: ModelConfig,
                                draft_params, draft_cfg: ModelConfig,
                                verify_state: dict, draft_state: dict,
                                branching: tuple, mesh=None):
    """Tree-mode fused step: the target verifies batch V's speculation
    tree (ancestor-masked, one forward over all ``n_nodes`` buffer rows)
    while the draft expands a fresh tree for batch D — one XLA program.

    verify_state: {target_cache, draft_cache, t_next, drafts} where
    ``drafts`` is the (B, N) BFS token buffer (row 0 == t_next).  Unlike
    the chain path there is no separate rollback call: both of batch V's
    caches are committed by accepted-path compaction *inside* the fused
    program (:func:`tree_commit_cache`), keeping the round at exactly one
    dispatch per rotation.
    """
    branching = tuple(branching)
    n_nodes = tree_n_nodes(branching)
    # --- target side: verify batch V's tree
    tlogits, tcache, _ = M.decode(
        target_params, target_cfg, verify_state["target_cache"],
        verify_state["drafts"], mesh, spec_tree=tree_spec(branching))
    a, nxt, out, path_idx = tree_greedy_acceptance(
        verify_state["drafts"], tlogits, branching)
    tcache = tree_commit_cache(target_cfg, tcache, path_idx, a, branching)
    vdcache = tree_commit_cache(draft_cfg, verify_state["draft_cache"],
                                path_idx, a, branching, pos_offset=n_nodes)

    # --- draft side: expand a tree for batch D (independent compute)
    drafts, _, dcache = draft_tree_generate(
        draft_params, draft_cfg, draft_state["draft_cache"],
        draft_state["t_next"], branching, mesh)

    verify_out = {"target_cache": tcache, "draft_cache": vdcache,
                  "tokens": out, "n_emitted": a + 1, "t_next": nxt,
                  "n_accept": a}
    draft_out = {"drafts": drafts, "draft_cache": dcache}
    return verify_out, draft_out
