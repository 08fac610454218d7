"""The control of ``correct``: program and int8-reference readings on seeds.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 11 12 13

Runs the cell once per seed in this one process, as ``bench/run.py``
would, and reads at the same checked positions both the program's numbers
and those of the reference computed in int8 put in the program's place.
Both are judged by the configuration's limits with the rule that sets
``correct``; one JSON line per seed gives ``correct`` (the program) and
``control_correct`` (the int8 reference), which has to come out false.
The limits lie between the program's largest reading over a dozen seeds
and the control's smallest.  Per-token readings go to
``bench_out/control/<workload>.<seed>.npz``.  The benchmark's own runs do
not run this.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    import numpy as np

    from bench import harness
    out = harness.OUT_DIR / "control"
    out.mkdir(parents=True, exist_ok=True)
    t0 = T0
    for seed in args.seeds:
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             t_process0=t0, control=True)
        np.savez(out / f"{args.workload}.{seed}.npz", **r.pop("arrays"))
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": r["control_correct"],
                          "checks": r["checks"], "control": r["control"],
                          "metrics": r["metrics"]}), flush=True)
        del r
        gc.collect()
        t0 = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
