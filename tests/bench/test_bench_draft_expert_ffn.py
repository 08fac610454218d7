"""``draft_expert_ffn_ms.offline`` on hand-made trace reductions: device
time of ``%routed_expert_ffn`` per fused execution, and no reading where
the program has no such kernel."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.xplane import Reduced  # noqa: E402

READ = harness.reader("draft_expert_ffn_ms.offline")


def _ctx(ops):
    programs = {"jit_fused_verify_and_draft(1)": (4, 300e6),
                "jit_rollback_draft(2)": (4, 2e6)}
    return {"trace": Reduced(window_ns=400e6, busy_ns=380e6,
                             programs=programs, ops=ops, gaps={},
                             longest_gaps=[])}


def test_sums_every_routed_call_per_fused_execution():
    # 4 executions x 5 draft steps: 20 calls in each of two HLO copies
    ops = {"%routed_expert_ffn.1": (20, 18e6),
           "%routed_expert_ffn.2": (20, 14e6),
           "%paged_mla_attention": (4, 2.4e6), "fusion.9": (4, 100e6)}
    assert READ(_ctx(ops)) == pytest.approx(8.0)


@pytest.mark.parametrize("ops", [
    {"_fusion.2396___bf16_64_16_2048_": (20, 1.2e9)},   # dense experts
    {"%paged_mla_attention": (4, 2.4e6)},
])
def test_no_reading_without_the_kernel(ops):
    assert READ(_ctx(ops)) is None


def test_no_reading_without_a_trace():
    assert READ({"trace": None}) is None
