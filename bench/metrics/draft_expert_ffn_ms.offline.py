"""Device time of the routed expert FFN kernel (``%routed_expert_ffn``)
per execution of the fused verify+draft program, from the profiler
trace, in ms: the draft's MoE layers in each of its decode steps.  None
where the trace holds no such kernel."""
from bench.xplane import matching

PROGRAM = r"fused_verify_and_draft"
KERNEL = r"^%routed_expert_ffn"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    n_exec, _ = matching(red.programs, PROGRAM)
    n_k, t_k = matching(red.ops, KERNEL)
    if not n_exec or not n_k:
        return None
    return t_k / n_exec / 1e6
