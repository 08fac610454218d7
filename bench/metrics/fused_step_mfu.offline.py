"""Useful model FLOPs of the fused round over its device time and the
chip's bf16 peak, in %.

Useful FLOPs per round (``bench/flops.py``): the target's routed (top-k)
weights times ``n_cand + 1`` tokens for each live slot it verifies, the
draft's weights times ``n_cand`` for each live slot it drafts for, and
attention over those slots' live context lengths.  Idle slots and the
dropless dispatch's padding do not count.  Rounds and device time are
those of the traced window; the mean per round over the mean device time
per execution."""
from bench import flops
from bench.xplane import matching

PROGRAM = r"fused_verify_and_draft"


def round_flops(ctx, rnd) -> float:
    doc, sv = ctx["config"], ctx["mix"]["serving"]
    n_cand = sv["n_cand"]
    plen = ctx["padded_len"]
    verify = {}
    for rid, k, _ in rnd.emits:
        verify.setdefault(rid, k)
    v_len = [plen[r] + k - 1 + n_cand + 1 for r, k in verify.items()]
    d_pos = [plen[r] + k - 1 for r, k in rnd.live.items()
             if r not in verify]
    return flops.fused_step_flops(doc["target"], doc["draft"], n_cand,
                                  v_len, d_pos)


def read(ctx):
    red, rounds = ctx["trace"], ctx["rounds"]
    if red is None or not rounds:
        return None
    n, t = matching(red.programs, PROGRAM)
    if not n or t <= 0:
        return None
    useful = sum(round_flops(ctx, r) for r in rounds) / len(rounds)
    return 100.0 * useful / (t / n / 1e9) / ctx["peaks"]["bf16_flops"]
