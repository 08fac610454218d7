"""Interleaved Batch Pipeline (paper §4.1): dual-batch rotation.

The paper runs two batches in anti-phase: in slot t_n the *target* verifies
batch 1 while the *draft* generates candidates for batch 0; the roles swap
in t_{n+1}.  On GPU this needs two processes + shared memory (paper App.
A.2); in JAX the same concurrency is expressed as ONE fused jit step that
contains both computations, which XLA may schedule side by side.  The
serving path keeps the target device-resident, so there are no
streamed-weight copies in the step yet for the draft to hide.

Stepwise API (continuous-batching ready)
----------------------------------------
The pipeline is externally drivable, one rotation round at a time:

* :meth:`InterleavedPipeline.warmup` — slot t_0 of the paper's Figure 4:
  draft candidates for one batch so it can be verified next round.
* :meth:`InterleavedPipeline.step` — one fused round: verify the batch
  that holds drafts while drafting for the other; returns a
  :class:`RoundOutput` with per-sequence emitted tokens.  The caller owns
  the rotation (swap the two states between calls) and may mutate
  per-slot state *between* steps — the verified batch's ``drafts`` is
  ``None`` on return, which is the safe window for a scheduler to retire
  finished sequences and splice newly prefilled ones into freed cache
  slots (see :mod:`repro.serving.engine`).
* :meth:`InterleavedPipeline.run` — the original blocking loop, now a
  thin driver over ``warmup`` + ``step``.

All shapes inside ``step`` are fixed by ``(batch, n_cand)``, so the fused
jit program compiles exactly once per pipeline regardless of how many
sequences retire or join across rounds (``trace_counts`` exposes the
compile tally for tests).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.spec_decode import (draft_generate, draft_tree_generate,
                                    greedy_acceptance, rollback_draft,
                                    tree_commit_cache, tree_greedy_acceptance,
                                    tree_n_nodes, tree_spec, tree_supported)
from repro.models import model as M
from repro.obs import NULL_OBS


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

    m = verify_state["drafts"].shape[1]
    out = jnp.where(jnp.arange(m)[None, :] < a[:, None],
                    verify_state["drafts"], 0)
    out = jnp.concatenate([out, jnp.zeros_like(a[:, None])], axis=1)
    out = jax.vmap(lambda row, i, t: row.at[i].set(t))(out, a, nxt)

    verify_out = {"target_cache": tcache, "tokens": out, "n_emitted": a + 1,
                  "t_next": nxt, "n_accept": a}
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


class InterleavedPipeline:
    """Dual-batch rotation, drivable one round at a time.

    Pure orchestration — all heavy work happens in jitted steps whose
    shapes depend only on ``(batch, n_cand)``.  ``trace_counts`` records
    how many times each jitted entry point was (re)traced; a scheduler
    that keeps shapes stable should see ``trace_counts['fused'] == 1``
    for the whole serving lifetime.
    """

    def __init__(self, target_params, target_cfg, draft_params, draft_cfg,
                 n_cand: int, mesh=None, obs=None, tree=None):
        self.tp, self.tcfg = target_params, target_cfg
        self.dp, self.dcfg = draft_params, draft_cfg
        self.n_cand = n_cand
        self.tree = tuple(tree) if tree is not None else None
        self.mesh = mesh
        self.obs = obs if obs is not None else NULL_OBS
        self.trace_counts = {"fused": 0, "draft": 0, "rollback": 0}
        self._exported_traces = {k: 0 for k in self.trace_counts}
        if self.tree is not None:
            for name, cfg in (("target", target_cfg), ("draft", draft_cfg)):
                if not tree_supported(cfg):
                    raise ValueError(
                        f"tree speculation requires an all-attention "
                        f"decoder-only {name} model (layer_pattern="
                        f"{cfg.layer_pattern!r})")
            tree_n_nodes(self.tree)          # validates shape and node cap
            self._fused = jax.jit(
                self._counted("fused", fused_tree_verify_and_draft),
                static_argnames=("target_cfg", "draft_cfg", "branching",
                                 "mesh"))
            self._draft_only = jax.jit(
                self._counted("draft", draft_tree_generate),
                static_argnames=("cfg", "branching", "mesh",
                                 "collect_logits"))
            self._rollback = None            # commit happens inside fused
            return
        self._fused = jax.jit(
            self._counted("fused", fused_verify_and_draft),
            static_argnames=("target_cfg", "draft_cfg", "n_cand", "mesh"))
        self._draft_only = jax.jit(
            self._counted("draft", draft_generate),
            static_argnames=("cfg", "n_cand", "mesh"))
        self._rollback = jax.jit(
            self._counted("rollback", rollback_draft),
            static_argnames=("cfg",))

    def _counted(self, name, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            self.trace_counts[name] += 1   # runs only while tracing
            return fn(*args, **kwargs)
        return wrapper

    def export_trace_counts(self, registry) -> None:
        """Sync ``trace_counts`` into ``pipeline_traces_total{entry=...}``
        counters (delta-based: safe to call repeatedly).  A shape-stable
        serving run must report ``entry="fused"`` == 1 through this path
        (regression-tested in tests/test_obs.py)."""
        ctr = registry.counter(
            "pipeline_traces_total",
            "jit (re)traces per pipeline entry point; fused must stay 1")
        for entry, n in self.trace_counts.items():
            delta = n - self._exported_traces[entry]
            if delta:
                ctr.inc(delta, entry=entry)
                self._exported_traces[entry] = n
            elif n == 0:
                ctr.inc(0, entry=entry)   # materialize the zero series

    def _fused_args(self, verify: BatchState, gen: BatchState) -> tuple:
        assert verify.drafts is not None, "verify batch has no staged drafts"
        vstate = {"target_cache": verify.target_cache,
                  "t_next": verify.t_next, "drafts": verify.drafts}
        if self.tree is not None:
            vstate["draft_cache"] = verify.draft_cache
        dstate = {"draft_cache": gen.draft_cache, "t_next": gen.t_next}
        return (self.tp, self.tcfg, self.dp, self.dcfg, vstate, dstate,
                self.tree if self.tree is not None else self.n_cand,
                self.mesh)

    def lower_fused(self, verify: BatchState, gen: BatchState):
        """The fused step lowered for these two states without running
        it (``.compile().as_text()`` shows the program each round
        executes).  ``verify`` must hold staged drafts."""
        return self._fused.lower(*self._fused_args(verify, gen))

    # ------------------------------------------------------------------
    def warmup(self, state: BatchState) -> None:
        """Slot t_0 (Fig. 4): draft candidates for ``state`` so the next
        :meth:`step` can verify it.  No-op if drafts are already staged."""
        if state.drafts is not None:
            return
        with self.obs.tracer.span("draft_generate", "warmup",
                                  cat="device") as sp:
            if self.tree is not None:
                d, _, dc = self._draft_only(self.dp, self.dcfg,
                                            state.draft_cache,
                                            state.t_next, self.tree)
                pend = None
            else:
                d, _, dc, pend = self._draft_only(self.dp, self.dcfg,
                                                  state.draft_cache,
                                                  state.t_next, self.n_cand)
            sp.fence(d)
        state.drafts, state.draft_cache, state.draft_pendings = d, dc, pend

    def step(self, verify: BatchState, gen: BatchState,
             record: bool = True) -> RoundOutput:
        """One rotation round: verify ``verify``'s staged drafts while
        drafting fresh candidates for ``gen`` (one fused XLA program).

        Mutates both states in place; on return ``verify.drafts is None``
        (the safe window for slot surgery) and ``gen`` holds new drafts.
        ``record=False`` skips appending to ``verify.emitted`` — use it
        when the caller does its own per-slot bookkeeping, so a
        long-running server doesn't grow the emitted log unboundedly.
        """
        assert gen.drafts is None, "gen batch already holds drafts"
        t_round0 = time.perf_counter()
        tr = self.obs.tracer
        # one XLA program verifies batch V and drafts for batch D
        with tr.span("target_verify", "verify(fused)", cat="device") as sp:
            vout, dout = self._fused(*self._fused_args(verify, gen))
            sp.fence((vout, dout))
        verify.target_cache = vout["target_cache"]
        if self.tree is not None:
            # batch V's draft cache was compacted to the accepted path
            # inside the fused program — no separate rollback dispatch.
            verify.draft_cache = vout["draft_cache"]
        else:
            # batch V: commit + roll its draft cache back to acceptance
            with tr.span("rollback", "rollback", cat="device") as rb:
                verify.draft_cache = rb.fence(self._rollback(
                    self.dcfg, verify.draft_cache, verify.draft_pendings,
                    vout["n_emitted"]))
        t_dispatched = time.perf_counter()
        verify.t_next = vout["t_next"]
        verify.drafts, verify.draft_pendings = None, None
        # Waits for the fused program, then copies its outputs to the
        # host.  Batch D's fresh drafts are stashed, and the round's
        # spent device arrays released, inside the span too: that host
        # work runs while the device idles, so it belongs to this phase.
        with tr.span("d2h", "outputs"):
            tokens = np.asarray(vout["tokens"])
            n_emitted = np.asarray(vout["n_emitted"])
            n_accept = np.asarray(vout["n_accept"])
            gen.drafts = dout["drafts"]
            gen.draft_cache = dout["draft_cache"]
            gen.draft_pendings = dout.get("pendings")
            del vout, dout
        out = RoundOutput(tokens=tokens, n_emitted=n_emitted,
                          n_accept=n_accept, t0=t_round0,
                          t_dispatched=t_dispatched, t1=time.perf_counter())
        if record:
            verify.emitted.append((out.tokens, out.n_emitted))
        return out

    def run(self, states: list, gen_len: int, max_rounds: int = 10_000):
        """Blocking driver: rotate until every sequence has ``gen_len``
        tokens.  states: two BatchState entries (prefilled); mutated and
        returned with ``emitted`` filled."""
        s0, s1 = states
        self.warmup(s0)

        def total(st):
            """Guaranteed tokens so far = sum of per-round minima."""
            return int(sum(np.min(np.asarray(n)) for _, n in st.emitted))

        verify, gen = s0, s1
        rounds = 0
        while rounds < max_rounds:
            if total(s0) >= gen_len and total(s1) >= gen_len:
                break
            self.step(verify, gen)
            verify, gen = gen, verify        # rotate roles (t_{n+1}, Fig. 4)
            rounds += 1
        return s0, s1, rounds
