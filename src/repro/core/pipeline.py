"""SpecOffloadEngine — the paper's full system (§3): offline placement +
online planning + the two-phase interleaved pipeline.

Usage (see examples/serve_spec_offload.py)::

    engine = SpecOffloadEngine(target_cfg, draft_cfg, hw=ENV1)
    engine.load(target_params, draft_params)
    out = engine.generate(prompts, gen_len=64)

Stepwise API
------------
``generate()`` is a convenience wrapper over three explicit phases, each
usable on its own (the continuous-batching scheduler in
:mod:`repro.serving.engine` drives them directly):

* :meth:`prefill_batch` — zig-zag microbatched prefill (§4.1.1) of a
  prompt batch into a fresh :class:`BatchState` (target + draft caches,
  first greedy token staged in ``t_next``).
* :meth:`decode_round` — one dual-batch rotation round (§4.1.2) via
  :class:`repro.core.interleave.InterleavedPipeline`; returns the
  verified batch's per-sequence tokens.
* :meth:`finalize` — assemble the per-round emission log of the two
  interleaved batches into a dense ``(B, gen_len)`` token array.

Phases
------
* **Prefill** (§4.1.1) — zig-zag microbatching: the prompt batch is split
  into ``bs_prefill`` chunks; each chunk runs a full prefill while the
  engine keeps only the streamed working set resident.  KV is then handed
  to the decode phase (host tier in the offloaded configuration).
* **Decode** (§4.1.2) — dual-batch rotation via
  :class:`repro.core.interleave.InterleavedPipeline`.

The engine is hardware-agnostic: on the CPU container it runs the real
algorithm end-to-end at small scale; placement/planner decisions use the
configured :class:`HardwareSpec`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.interleave import (BatchState, InterleavedPipeline,
                                   RoundOutput)
from repro.core.placement import PlacementPlan, plan_placement
from repro.core.planner import ParaSpecPlanner, Policy, Workload
from repro.models import model as M
from repro.models.transformer import init_cache
from repro.obs import NULL_OBS
from repro.sim.hardware import ENV1, HardwareSpec


def required_cache_len(prompt_len: int, gen_len: int, n_cand: int) -> int:
    """Per-sequence KV capacity for a decode of ``gen_len`` tokens: the
    last speculative round can overshoot the target length, and the draft
    cache briefly holds ``n_cand + 1`` uncommitted positions before
    rollback.  Shared by generate() and the serving scheduler so their
    capacity checks can never diverge."""
    return prompt_len + gen_len + 3 * (n_cand + 1) + 4


@dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, gen_len)
    rounds: int
    accept_counts: list
    policy: Policy
    placement: PlacementPlan


class SpecOffloadEngine:
    def __init__(self, target_cfg: ModelConfig, draft_cfg: ModelConfig,
                 hw: HardwareSpec = ENV1, policy: Policy | None = None,
                 mesh=None, obs=None):
        self.tcfg = target_cfg
        self.dcfg = draft_cfg
        self.hw = hw
        self.mesh = mesh
        self.obs = obs if obs is not None else NULL_OBS
        self.policy = policy
        self.placement = plan_placement(target_cfg, draft_cfg, hw)
        self.tp = None
        self.dp = None
        self._prefill = jax.jit(M.prefill, static_argnums=(1,),
                                static_argnames=("mesh",))
        self._pipe: InterleavedPipeline | None = None

    # ------------------------------------------------------------------
    def load(self, target_params, draft_params):
        self.tp = target_params
        self.dp = draft_params
        self._pipe = None

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

    def plan(self, prompt_len: int, gen_len: int,
             accept_prob: float = 0.7, occupancy: float = 1.0) -> Policy:
        if self.policy is not None:
            return self.policy
        planner = ParaSpecPlanner(self.tcfg, self.dcfg, self.hw,
                                  obs=self.obs)
        rep = planner.search(Workload(prompt_len, gen_len, accept_prob,
                                      occupancy))
        self.policy = rep.policy
        return self.policy

    # ------------------------------------------------------------------
    def _prefill_zigzag(self, params, cfg, tokens: jax.Array,
                        bs_prefill: int, max_len: int):
        """Microbatched prefill (zig-zag §4.1.1): the batch is processed in
        ``bs_prefill`` chunks so only one chunk's activations + KV are live
        on the accelerator at a time; chunk caches are then concatenated
        (the paper ships them to host memory between chunks)."""
        b = tokens.shape[0]
        last_logits, caches = [], []
        for i in range(0, b, bs_prefill):
            chunk = tokens[i:i + bs_prefill]
            c = init_cache(cfg, chunk.shape[0], max_len)
            lg, c = self._prefill(params, cfg, chunk, c)
            last_logits.append(lg)
            caches.append(c)
        if len(caches) == 1:
            return last_logits[0], caches[0]
        return jnp.concatenate(last_logits, 0), _concat_caches(caches)

    # ------------------------------------------------------------------
    # stepwise API

    def prefill_batch(self, prompts: jax.Array, max_len: int,
                      bs_prefill: int | None = None) -> BatchState:
        """Zig-zag prefill of a ``(B, L)`` prompt batch into a fresh
        :class:`BatchState` with KV capacity ``max_len`` per sequence.

        The first greedy token (argmax over the prefill's last logits) is
        staged in ``t_next`` and recorded as the first emission, exactly
        as a target-only greedy decode would start.
        """
        assert self.tp is not None, "call load()/init_from_seed() first"
        bs_prefill = bs_prefill or max(1, prompts.shape[0])
        with self.obs.tracer.span("prefill", "zigzag_prefill",
                                  cat="device") as sp:
            lg, tc = self._prefill_zigzag(self.tp, self.tcfg, prompts,
                                          bs_prefill, max_len)
            _, dc = self._prefill_zigzag(self.dp, self.dcfg, prompts,
                                         bs_prefill, max_len)
            sp.fence((lg, tc, dc))
            sp.set("batch", int(prompts.shape[0]))
            sp.set("prompt_len", int(prompts.shape[1]))
        t0 = jnp.argmax(lg, -1)
        return BatchState(target_cache=tc, draft_cache=dc, t_next=t0,
                          drafts=None, draft_pendings=None,
                          emitted=[(np.asarray(t0)[:, None], 1)])

    def pipeline(self, n_cand: int, tree=None) -> InterleavedPipeline:
        """The (cached) dual-batch rotation pipeline for ``n_cand`` —
        or, when ``tree`` (a branching tuple) is given, the tree-mode
        pipeline with that speculation-tree shape."""
        assert self.tp is not None, "call load()/init_from_seed() first"
        tree = tuple(tree) if tree is not None else None
        if (self._pipe is None or self._pipe.n_cand != n_cand
                or self._pipe.tree != tree):
            self._pipe = InterleavedPipeline(self.tp, self.tcfg, self.dp,
                                             self.dcfg, n_cand, self.mesh,
                                             obs=self.obs, tree=tree)
        return self._pipe

    def decode_round(self, verify: BatchState, gen: BatchState,
                     n_cand: int, record: bool = True,
                     tree=None) -> RoundOutput:
        """One rotation round: verify ``verify``, draft for ``gen``.
        Swap the two states between calls to rotate roles; see
        :meth:`InterleavedPipeline.step` for the slot-surgery window."""
        pipe = self.pipeline(n_cand, tree=tree)
        pipe.warmup(verify)
        return pipe.step(verify, gen, record=record)

    def finalize(self, states: list, gen_len: int) -> tuple:
        """Assemble the two interleaved batches' emission logs into a
        dense ``(B_total, gen_len)`` array (+ per-round accept counts)."""
        widths = [int(np.asarray(st.emitted[0][0]).shape[0])
                  for st in states]
        out = np.zeros((sum(widths), gen_len), np.int32)
        accepts = []
        row0 = 0
        for st, width in zip(states, widths):
            fills = [list() for _ in range(width)]
            for toks, n in st.emitted:
                toks = np.asarray(toks)
                n = np.asarray(n) + np.zeros(toks.shape[0], np.int32)
                for r in range(toks.shape[0]):
                    fills[r].extend(toks[r, :int(n[r])].tolist())
                if toks.shape[1] > 1:
                    accepts.append(n - 1)
            for r, f in enumerate(fills):
                out[row0 + r] = (f + [0] * gen_len)[:gen_len]
            row0 += width
        return out, accepts

    # ------------------------------------------------------------------
    def generate(self, prompts: jax.Array, gen_len: int, n_cand: int = 4,
                 max_len: int | None = None) -> GenerationResult:
        """prompts (B, L) int32, B split into the two interleaved batches.

        Convenience wrapper: prefill both halves, rotate decode rounds
        until every sequence has ``gen_len`` tokens, finalize."""
        assert self.tp is not None, "call load()/init_from_seed() first"
        b, length = prompts.shape
        pol = self.policy or Policy(bs_prefill=max(1, b // 2),
                                    bs_decode=max(1, b // 2),
                                    bs_draft=max(1, b // 2), n_cand=n_cand)
        m = pol.n_cand
        max_len = max_len or required_cache_len(length, gen_len, m)

        half = b // 2
        states = [self.prefill_batch(bt, max_len, pol.bs_prefill)
                  for bt in (prompts[:half], prompts[half:])]

        pipe = self.pipeline(m)
        s0, s1, rounds = pipe.run(states, gen_len)

        out, accepts = self.finalize([s0, s1], gen_len)
        return GenerationResult(out, rounds, accepts, pol, self.placement)


def _concat_caches(caches):
    """Concat per-chunk caches over the batch axis (axis 1 for stacked
    layer leaves, axis 0 for 'pos')."""
    layers = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                          *[c["layers"] for c in caches])
    pos = jnp.concatenate([c["pos"] for c in caches], axis=0)
    return {"layers": layers, "pos": pos}
