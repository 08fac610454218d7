"""Host seconds per fused round spent in series with the device, in ms:
from one round's outputs reaching the host to the next round's dispatch,
admissions excluded (program counter ``serve_host_serial_seconds_total``
over ``serve_fused_rounds_total``).  The excess of host-phase stalls
(``serve_stall_host_seconds_total``) is taken off, so the profiler's
start and stop inside a traced window do not count as host work.  Prints
on stderr how the window's seconds divide among the four host phases
(dispatch, fetch, host serial, admission) and the stall excess counted
in each of the three round phases."""
import sys


def read(ctx):
    c0, c1 = ctx["counters"]
    d = lambda k: c1.get(k, 0.0) - c0.get(k, 0.0)
    rounds = d("serve_fused_rounds_total")
    if rounds <= 0:
        return None
    parts = {p: d(f"serve_{p}_seconds_total")
             for p in ("dispatch", "fetch", "host_serial", "admit")}
    stalls = {p: d(f"serve_stall_{p}_seconds_total")
              for p in ("dispatch", "fetch", "host")}
    total, window = sum(parts.values()), ctx["window_s"]
    print(f"host_serial_ms: {rounds:.0f} rounds; " + ", ".join(
        f"{p} {s:.3f}s" for p, s in parts.items())
        + f"; sum {total:.3f}s of a {window:.3f}s window "
        f"({100 * total / window:.2f}%); stalls " + ", ".join(
            f"{p} {s:.3f}s" for p, s in stalls.items()), file=sys.stderr)
    return 1e3 * (parts["host_serial"] - stalls["host"]) / rounds
