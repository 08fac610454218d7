"""Benchmark regression gate: compare a fresh ``serving_bench`` obs
digest against the committed ``BENCH_serving_obs.json`` baseline.

CI runs the obs benchmark on every push; this gate turns its digest
into a pass/fail signal with explicit, documented tolerances instead of
an eyeballed JSON diff:

* ``tok_per_s`` (traced + untraced) must stay above
  ``tol_throughput`` x baseline (default 0.35 — shared CI runners are
  noisy; the gate catches collapses, not jitter).
* TTFT p50/p95 must stay below ``tol_latency`` x baseline (default
  3.0).
* ``untraced_fused_compiles`` must not exceed the baseline: a second
  fused-step compile is a hard architectural regression (shape leak),
  never hardware noise — no tolerance.

Override knob: ``--override`` (or ``BENCH_COMPARE_OVERRIDE=1`` in the
environment) downgrades a failure to a warning + zero exit, for
intentional baseline-moving changes — refresh the committed baseline in
the same PR.

    PYTHONPATH=src python -m benchmarks.bench_compare \\
        --baseline BENCH_serving_obs.json --current /tmp/obs_digest.json
    # regenerate the current digest inline (same params as the baseline)
    PYTHONPATH=src python -m benchmarks.bench_compare --run
"""
from __future__ import annotations

import json
import os

#: (check name, digest path, kind, default tolerance).  Kinds:
#: ``min_ratio`` — current >= tol * baseline;
#: ``max_ratio`` — current <= tol * baseline;
#: ``max_value`` — current <= baseline (tol unused; exactness gates).
CHECKS = (
    ("untraced_tok_per_s", ("untraced_tok_per_s",), "min_ratio",
     "tol_throughput"),
    ("traced_tok_per_s", ("traced_tok_per_s",), "min_ratio",
     "tol_throughput"),
    ("ttft_p50_s", ("ttft", "p50"), "max_ratio", "tol_latency"),
    ("ttft_p95_s", ("ttft", "p95"), "max_ratio", "tol_latency"),
    ("fused_compiles", ("untraced_fused_compiles",), "max_value", None),
)

DEFAULT_TOLERANCES = {"tol_throughput": 0.35, "tol_latency": 3.0}


def _lookup(digest: dict, path: tuple):
    cur = digest
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    try:
        return float(cur)
    except (TypeError, ValueError):
        return None


def compare_digests(baseline: dict, current: dict,
                    tolerances: dict | None = None) -> dict:
    """Evaluate every check; returns ``{"ok", "checks": [...]}``.

    A metric missing from the *baseline* is skipped (legacy baseline —
    refresh it); missing from the *current* digest it fails (the bench
    stopped producing it, which is itself a regression).
    """
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(tolerances or {})
    checks, ok = [], True
    for name, path, kind, tol_key in CHECKS:
        base = _lookup(baseline, path)
        cur = _lookup(current, path)
        entry = {"name": name, "kind": kind, "baseline": base,
                 "current": cur,
                 "tolerance": tol[tol_key] if tol_key else None}
        if base is None or base != base:
            entry["ok"], entry["note"] = True, "skipped: not in baseline"
        elif cur is None or cur != cur:
            entry["ok"], entry["note"] = False, "missing from current"
        elif kind == "min_ratio":
            limit = tol[tol_key] * base
            entry["limit"] = limit
            entry["ok"] = cur >= limit
        elif kind == "max_ratio":
            limit = tol[tol_key] * base
            entry["limit"] = limit
            entry["ok"] = cur <= limit
        else:                                    # max_value: exactness
            entry["limit"] = base
            entry["ok"] = cur <= base
        ok = ok and entry["ok"]
        checks.append(entry)
    return {"ok": ok, "checks": checks}


def _fmt(v) -> str:
    return "-" if v is None else f"{v:.4g}"


def print_report(report: dict):
    for c in report["checks"]:
        mark = "ok  " if c["ok"] else "FAIL"
        note = f"  ({c['note']})" if c.get("note") else ""
        print(f"  [{mark}] {c['name']:<22} current={_fmt(c['current'])}"
              f"  baseline={_fmt(c['baseline'])}"
              f"  limit={_fmt(c.get('limit'))}{note}")


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default="BENCH_serving_obs.json",
                    help="committed digest to gate against")
    ap.add_argument("--current", default="/tmp/obs_digest.json",
                    help="fresh digest to evaluate")
    ap.add_argument("--run", action="store_true",
                    help="regenerate --current inline via "
                         "serving_bench.obs_run (default bench params)")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--tol-throughput", type=float,
                    default=DEFAULT_TOLERANCES["tol_throughput"],
                    help="min tok/s ratio vs baseline")
    ap.add_argument("--tol-latency", type=float,
                    default=DEFAULT_TOLERANCES["tol_latency"],
                    help="max TTFT ratio vs baseline")
    ap.add_argument("--override", action="store_true",
                    help="report failures but exit 0 (baseline-moving "
                         "change; refresh the baseline in the same PR). "
                         "BENCH_COMPARE_OVERRIDE=1 does the same")
    args = ap.parse_args()

    if args.run:
        from benchmarks.serving_bench import obs_run
        current = obs_run(args.requests, args.gen)
        with open(args.current, "w") as f:
            json.dump(current, f, indent=2)
    else:
        with open(args.current) as f:
            current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)

    report = compare_digests(baseline, current,
                             {"tol_throughput": args.tol_throughput,
                              "tol_latency": args.tol_latency})
    print(f"bench_compare: {args.current} vs {args.baseline}")
    print_report(report)
    override = args.override or bool(os.environ.get(
        "BENCH_COMPARE_OVERRIDE"))
    if report["ok"]:
        print("bench_compare: PASS")
    elif override:
        print("bench_compare: FAIL (overridden — refresh the committed "
              "baseline in this PR)")
    else:
        print("bench_compare: FAIL")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
