import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
combination on the production meshes, prove per-chip memory fits, and
extract the roofline terms from the compiled artifact.

The two lines above MUST precede every other import (jax locks the device
count on first init).  Do not import this module from test/bench processes
that need a single device — run it as a subprocess:

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
        --shape decode_32k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per combination the dry-run records (benchmarks/results/dryrun/*.json):
  - lower+compile success,
  - compiled.memory_analysis()  (bytes/device — proves it fits 16 GB),
  - compiled.cost_analysis()    (HLO FLOPs / bytes for §Roofline),
  - collective bytes parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute),
  - the derived roofline terms (see benchmarks/roofline.py).
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / \
    "results" / "dryrun"

COLLECTIVE_RE = re.compile(
    r"(\w\d+(?:\[[\d,]*\])?(?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
SHAPE_RE = re.compile(r"([a-z]+?)(\d*)\[([\d,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
               "s32": 4, "u32": 4, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
               "s8": 1, "u8": 1, "pred": 1}


def shape_bytes(shape_str: str) -> int:
    m = SHAPE_RE.match(shape_str.replace(" ", ""))
    if not m:
        return 0
    kind, bits, dims = m.groups()
    nbytes = max(int(bits) // 8, 1) if bits else 1  # pred/f8 -> 1 byte
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nbytes


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes per collective kind from optimized HLO.

    Convention: we charge each collective its RESULT size (equal to the
    operand size for all-reduce; the gathered size for all-gather; the
    scattered size for reduce-scatter) — documented in EXPERIMENTS.md.
    """
    totals = {}
    counts = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        # lines look like: %name = bf16[8,128]{1,0} all-gather(...)
        m = re.search(
            r"=\s+(?:\()?([a-z]+\d*\[[\d,]*\][^ ]*)\s+"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        sh, kind = m.groups()
        b = shape_bytes(sh)
        totals[kind] = totals.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes": totals, "counts": counts,
            "total_bytes": sum(totals.values())}


def run_one(arch: str, shape_name: str, mesh_kind: str,
            kv_int8: bool = False) -> dict:
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.configs.base import INPUT_SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import applicable, build_step

    cfg = get_config(arch)
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    shape = INPUT_SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "phase": shape.phase}
    if not ok:
        rec.update(status="skip", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = 512 if mesh_kind == "multi" else 256
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args, donate = build_step(cfg, shape, mesh)
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        mem_rec = {}
        for k in ("generated_code_size_in_bytes",
                  "argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_rec[k] = int(v)
        cost = compiled.cost_analysis() or {}
        cost_rec = {k: float(v) for k, v in cost.items()
                    if isinstance(v, (int, float)) and (
                        "flops" in k or "bytes" in k or k in ("utilization",))}

        hlo = compiled.as_text()
        coll = parse_collectives(hlo)

    per_dev_bytes = (mem_rec.get("argument_size_in_bytes", 0)
                     + mem_rec.get("output_size_in_bytes", 0)
                     + mem_rec.get("temp_size_in_bytes", 0)
                     - mem_rec.get("alias_size_in_bytes", 0))
    rec.update(
        status="ok", n_devices=n_dev,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory=mem_rec, per_device_bytes=per_dev_bytes,
        per_device_gib=round(per_dev_bytes / 2**30, 3),
        fits_16gib=bool(per_dev_bytes <= 16 * 2**30),
        cost=cost_rec, collectives=coll,
    )
    return rec


def result_path(arch, shape, mesh_kind):
    return RESULTS_DIR / mesh_kind / f"{arch}__{shape}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) as subprocesses")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantized KV cache (results not cached)")
    args = ap.parse_args()

    if args.all:
        from repro.configs import ARCHS
        from repro.configs.base import INPUT_SHAPES
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        combos = [(a, s, m) for m in meshes for a in ARCHS
                  for s in INPUT_SHAPES]
        failures = []
        for a, s, m in combos:
            out = result_path(a, s, m)
            if out.exists() and not args.force:
                print(f"[skip-cached] {m} {a} {s}")
                continue
            print(f"[run] {m:6s} {a:28s} {s}", flush=True)
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", a, "--shape", s, "--mesh", m],
                capture_output=True, text=True)
            if r.returncode != 0:
                failures.append((a, s, m))
                print(r.stdout[-2000:])
                print(r.stderr[-4000:])
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    rec = run_one(args.arch, args.shape, args.mesh, kv_int8=args.kv_int8)
    if not args.kv_int8:   # variants are printed, not cached
        out = result_path(args.arch, args.shape, args.mesh)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rec, indent=1))
    print(json.dumps({k: rec[k] for k in rec
                      if k not in ("cost", "memory", "collectives")},
                     indent=1))
    if rec["status"] == "ok":
        print("memory:", rec["memory"])
        print("cost (flops/bytes):",
              {k: v for k, v in rec["cost"].items()
               if k in ("flops", "bytes accessed")})
        print("collectives:", rec["collectives"]["bytes"],
              "total=%.3f GiB" % (rec["collectives"]["total_bytes"] / 2**30))


if __name__ == "__main__":
    main()
