"""SpecOffload core: the paper's contribution as composable JAX modules.

- ``spec_decode``  — draft-then-verify speculative decoding (+ Appendix A.1
  acceptance model, with the Eq. 12 erratum corrected).
- ``interleave``   — the dual-batch Interleaved Batch Pipeline (§4.1): batch
  state and the fused verify+draft programs.
- ``placement``    — Adaptive Tensor Placement across HBM/host/disk (§4.2).
- ``planner``      — ParaSpec policy planner (§4.3).
- ``offload``      — host<->HBM weight streaming with memory_kind tiers.
- ``pipeline``     — SpecOffloadEngine, the round runner that jits and
  dispatches those programs for the serving scheduler.
"""
from repro.core.interleave import (BatchState, RoundOutput,
                                   fused_verify_and_draft)
from repro.core.pipeline import SpecOffloadEngine
from repro.core.placement import PlacementPlan, plan_placement
from repro.core.planner import ParaSpecPlanner, Policy, Workload
from repro.core.spec_decode import (expected_generated, greedy_acceptance,
                                    sampled_acceptance, spec_round)

__all__ = [
    "BatchState", "RoundOutput",
    "fused_verify_and_draft", "SpecOffloadEngine",
    "PlacementPlan", "plan_placement", "ParaSpecPlanner", "Policy",
    "Workload", "expected_generated", "greedy_acceptance",
    "sampled_acceptance", "spec_round",
]
