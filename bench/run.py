"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  Exits non-zero, printing no result, unless JAX's devices
are TPUs and there are enough of them.  With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of part of the window.  The last lines
on standard error are the numbers that decide ``correct``, each beside
its limit; they are also the result's last key, ``checks``.
"""
import time

T_PROCESS0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    bench = harness.load_benchmark()
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    if args.workload not in chips:
        print(f"no workload {args.workload!r}", file=sys.stderr)
        return 2

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < chips[args.workload]:
        print(f"needs {chips[args.workload]} chips; JAX found {len(devs)}",
              file=sys.stderr)
        return 1
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process0=T_PROCESS0,
                              bench=bench)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
