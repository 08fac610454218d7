"""SpecOffloadEngine — the round runner between the continuous-batching
scheduler (:mod:`repro.serving.engine`) and the fused programs
(:mod:`repro.core.interleave`).

It holds both models' weights and builds every jitted program once:
prefill, the chain and tree draft-only programs, the chain and tree fused
verify+draft programs, and the chain's rollback.  ``jax.jit``'s static
arguments key compiles by ``n_cand`` / branching, so one runner serves
either speculation mode.

* :meth:`prefill_batch` — prefill of a prompt batch (§4.1.1) into a fresh
  :class:`BatchState` (target + draft caches, first greedy token staged
  in ``t_next``).
* :meth:`warmup` — slot t_0 of the paper's Figure 4: draft candidates for
  one batch so it can be verified next round.
* :meth:`decode_round` — one dual-batch rotation round (§4.1.2): verify
  the batch that holds drafts while drafting for the other, one fused
  XLA program; returns the verified batch's per-sequence tokens.  The
  caller owns the rotation (swap the two states between calls) and may
  mutate per-slot state *between* rounds — the verified batch's
  ``drafts`` is ``None`` on return, the safe window for a scheduler to
  retire finished sequences and splice new ones into freed cache slots.

All shapes inside a round are fixed by ``(batch, n_cand)`` (or the tree
shape), so the fused program compiles exactly once however many
sequences retire or join across rounds (``trace_counts`` keeps the tally).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.interleave import (BatchState, RoundOutput,
                                   fused_tree_verify_and_draft,
                                   fused_verify_and_draft)
from repro.core.spec_decode import (draft_generate, draft_tree_generate,
                                    rollback_draft)
from repro.models import model as M
from repro.models.transformer import init_cache
from repro.obs import NULL_OBS


def required_cache_len(prompt_len: int, gen_len: int, n_cand: int) -> int:
    """Per-sequence KV capacity for a decode of ``gen_len`` tokens: the
    last speculative round can overshoot the target length, and the draft
    cache briefly holds ``n_cand + 1`` uncommitted positions before
    rollback.  Shared by the serving scheduler's admission checks and the
    benchmark's cache sizing so the two can never diverge."""
    return prompt_len + gen_len + 3 * (n_cand + 1) + 4


class SpecOffloadEngine:
    """Dual-batch rotation over two resident models, one round at a time.

    ``trace_counts`` records how many times each jitted entry point was
    (re)traced; a scheduler that keeps shapes stable sees
    ``trace_counts['fused'] == 1`` for the whole serving lifetime.
    """

    def __init__(self, target_cfg: ModelConfig, draft_cfg: ModelConfig,
                 obs=None):
        self.tcfg = target_cfg
        self.dcfg = draft_cfg
        self.obs = obs if obs is not None else NULL_OBS
        self.tp = None
        self.dp = None
        self.trace_counts = {"fused": 0, "draft": 0, "rollback": 0}
        self._exported_traces = {k: 0 for k in self.trace_counts}
        self._prefill = jax.jit(M.prefill, static_argnums=(1,),
                                static_argnames=("mesh",))
        self._fused = jax.jit(
            self._counted("fused", fused_verify_and_draft),
            static_argnames=("target_cfg", "draft_cfg", "n_cand", "mesh"))
        self._fused_tree = jax.jit(
            self._counted("fused", fused_tree_verify_and_draft),
            static_argnames=("target_cfg", "draft_cfg", "branching",
                             "mesh"))
        self._draft = jax.jit(
            self._counted("draft", draft_generate),
            static_argnames=("cfg", "n_cand", "mesh"))
        self._draft_tree = jax.jit(
            self._counted("draft", draft_tree_generate),
            static_argnames=("cfg", "branching", "mesh", "collect_logits"))
        # tree mode commits both caches inside the fused program
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

    # ------------------------------------------------------------------
    def load(self, target_params, draft_params):
        self.tp = target_params
        self.dp = draft_params

    def init_from_seed(self, seed: int = 0):
        """Random weights from ``seed``, built under ``jax.jit`` one model
        at a time, so the float32 sampling temporaries of one model are
        freed before the next is built (eager init keeps several alive
        beside the finished leaves and overflows 16 GB at published
        widths)."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        init = jax.jit(M.init_params, static_argnums=0)
        tp = jax.block_until_ready(init(self.tcfg, k1))
        self.load(tp, init(self.dcfg, k2))

    # ------------------------------------------------------------------
    def prefill_batch(self, prompts: jax.Array, max_len: int) -> BatchState:
        """Prefill of a ``(B, L)`` prompt batch into a fresh
        :class:`BatchState` with KV capacity ``max_len`` per sequence.

        The first greedy token (argmax over the prefill's last logits) is
        staged in ``t_next`` and recorded as the first emission, exactly
        as a target-only greedy decode would start.
        """
        assert self.tp is not None, "call load()/init_from_seed() first"
        b = prompts.shape[0]
        with self.obs.tracer.span("prefill", "zigzag_prefill",
                                  cat="device") as sp:
            lg, tc = self._prefill(self.tp, self.tcfg, prompts,
                                   init_cache(self.tcfg, b, max_len))
            _, dc = self._prefill(self.dp, self.dcfg, prompts,
                                  init_cache(self.dcfg, b, max_len))
            sp.fence((lg, tc, dc))
            sp.set("batch", int(b))
            sp.set("prompt_len", int(prompts.shape[1]))
        t0 = jnp.argmax(lg, -1)
        return BatchState(target_cache=tc, draft_cache=dc, t_next=t0,
                          drafts=None, draft_pendings=None,
                          emitted=[(np.asarray(t0)[:, None], 1)])

    def warmup(self, state: BatchState, n_cand: int, tree=None) -> None:
        """Slot t_0 (Fig. 4): draft candidates for ``state`` so the next
        round can verify it.  No-op if drafts are already staged."""
        if state.drafts is not None:
            return
        with self.obs.tracer.span("draft_generate", "warmup",
                                  cat="device") as sp:
            if tree is not None:
                d, _, dc = self._draft_tree(self.dp, self.dcfg,
                                            state.draft_cache,
                                            state.t_next, tree)
                pend = None
            else:
                d, _, dc, pend = self._draft(self.dp, self.dcfg,
                                             state.draft_cache,
                                             state.t_next, n_cand)
            sp.fence(d)
        state.drafts, state.draft_cache, state.draft_pendings = d, dc, pend

    def _fused_call(self, verify: BatchState, gen: BatchState, n_cand: int,
                    tree) -> tuple:
        """The fused program for the mode and its arguments."""
        assert verify.drafts is not None, "verify batch has no staged drafts"
        vstate = {"target_cache": verify.target_cache,
                  "t_next": verify.t_next, "drafts": verify.drafts}
        dstate = {"draft_cache": gen.draft_cache, "t_next": gen.t_next}
        if tree is None:
            fused, shape = self._fused, n_cand
        else:
            vstate["draft_cache"] = verify.draft_cache
            fused, shape = self._fused_tree, tree
        return fused, (self.tp, self.tcfg, self.dp, self.dcfg, vstate,
                       dstate, shape)

    def lower_fused(self, verify: BatchState, gen: BatchState, n_cand: int,
                    tree=None):
        """The fused round lowered for these two states without running
        it (``.compile().as_text()`` shows the program each round
        executes).  Stages ``verify``'s drafts first if it has none."""
        self.warmup(verify, n_cand, tree)
        fused, args = self._fused_call(verify, gen, n_cand, tree)
        return fused.lower(*args)

    def decode_round(self, verify: BatchState, gen: BatchState,
                     n_cand: int, record: bool = True,
                     tree=None) -> RoundOutput:
        """One rotation round: verify ``verify``'s staged drafts (drafting
        them first if none are staged) while drafting fresh candidates for
        ``gen``, in one fused XLA program; ``tree`` (a branching tuple)
        selects tree speculation.

        Mutates both states in place; on return ``verify.drafts is None``
        (the safe window for slot surgery) and ``gen`` holds new drafts.
        ``record=False`` skips appending to ``verify.emitted`` — use it
        when the caller does its own per-slot bookkeeping, so a
        long-running server doesn't grow the emitted log unboundedly.
        """
        self.warmup(verify, n_cand, tree)
        assert gen.drafts is None, "gen batch already holds drafts"
        t_round0 = time.perf_counter()
        tr = self.obs.tracer
        # one XLA program verifies batch V and drafts for batch D
        with tr.span("target_verify", "verify(fused)", cat="device") as sp:
            fused, args = self._fused_call(verify, gen, n_cand, tree)
            vout, dout = fused(*args)
            sp.fence((vout, dout))
        verify.target_cache = vout["target_cache"]
        if tree is not None:
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
