"""Host seconds per fused round in the calls that dispatch the fused and
rollback programs, in ms (program counter ``serve_dispatch_seconds_total``
over ``serve_fused_rounds_total``), with the excess of dispatch stalls
(``serve_stall_dispatch_seconds_total``) left out."""


def read(ctx):
    c0, c1 = ctx["counters"]
    d = lambda k: c1.get(k, 0.0) - c0.get(k, 0.0)
    rounds = d("serve_fused_rounds_total")
    if rounds <= 0:
        return None
    return 1e3 * (d("serve_dispatch_seconds_total")
                  - d("serve_stall_dispatch_seconds_total")) / rounds
