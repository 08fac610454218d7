"""The paged verify-attention kernel's share of its roofline, in %.

For each traced round: the least time the chip could take for the
kernel's calls, the larger of FLOPs over the bf16 peak and bytes over the
HBM bandwidth, with FLOPs and bytes from the live slots' context lengths
(``bench/flops.py``; one call per target layer).  The mean of that over
the rounds, divided by the kernel's mean device time per fused execution
from the trace."""
import sys

from bench import flops
from bench.xplane import matching

PROGRAM = r"fused_verify_and_draft"
KERNEL = r"^%paged_decode_attention"


def read(ctx):
    red, rounds = ctx["trace"], ctx["rounds"]
    if red is None or not rounds:
        return None
    n_exec, _ = matching(red.programs, PROGRAM)
    n_k, t_k = matching(red.ops, KERNEL)
    if not n_exec or not n_k or t_k <= 0:
        return None
    tgt, pk = ctx["config"]["target"], ctx["peaks"]
    m = ctx["mix"]["serving"]["n_cand"] + 1
    plen = ctx["padded_len"]
    least, bound = 0.0, {"flops": 0, "bytes": 0}
    for rnd in rounds:
        verify = {}
        for rid, k, _ in rnd.emits:
            verify.setdefault(rid, k)
        lengths = [plen[r] + k - 1 + m for r, k in verify.items()]
        f, b = flops.paged_attn_cost(tgt, m, lengths)
        f, b = f * flops.attn_layers(tgt), b * flops.attn_layers(tgt)
        tf, tb = f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"]
        least += max(tf, tb)
        bound["flops" if tf >= tb else "bytes"] += 1
    least /= len(rounds)
    per_exec = t_k / n_exec / 1e9
    print(f"paged_attn_roofline: {n_k} kernel calls in {n_exec} fused "
          f"executions; bound by {max(bound, key=bound.get)} in "
          f"{max(bound.values())}/{len(rounds)} rounds", file=sys.stderr)
    return 100.0 * least / per_exec
