"""Serve the Mixtral-8x7B / Mistral-7B pair at published widths on one TPU.

    python chip_smoke.py

One process, no subprocesses.  Phases, in order:

1. Device check: exits non-zero before any work unless JAX's first
   device is a TPU.
2. Build the engine exactly as ``python -m repro.launch.serve`` does
   (``SERVE_ARGS``), with random weights from seed 0.
3. Serve 8 seeded Poisson requests closed-loop with ``ServingEngine.run()``.
4. Stream 2 more requests through ``AsyncServingServer``.
5. Checks (any failure exits non-zero): one fused compile; every request
   got its token count; the compiled fused step holds the Pallas kernel
   (``tpu_custom_call``); the paged kernel at the served widths matches
   ``kernels/ref.py``; one prefill plus one paged decode step of the
   target matches a float32 reference forward of the same tokens.

Earlier lines report the device, compile and serve seconds, tokens served,
peak device memory and the memory kinds device 0 offers.  The last line
of stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

PAIR = "mixtral-8x7b-v5e-pair"
SERVE_ARGS = ["--arch", PAIR, "--no-reduced", "--env", "v5e",
              "--batch", "8", "--n-cand", "4", "--length-bucket", "256",
              "--async"]
SEED = 0
N_CLOSED, N_ASYNC = 8, 2
RATE_RPS = 4.0
PEAK_LIMIT = 16e9          # bytes; one v5e chip has 16 GB of HBM

# Paged kernel vs kernels/ref.py (float32 math on the same bf16 inputs).
# The kernel rounds its output to bf16 (<= 2^-9 relative), and a bf16
# MXU pass over the float32 softmax weights costs <= 2^-9 * sum(p|v|),
# about 0.009 for N(0, 1) values: atol and rtol of 1e-2 cover both.  A
# wrong block, mask or head mapping errs by 0.1 or more.
KERNEL_TOL = dict(atol=1e-2, rtol=1e-2)
# bf16 served path vs float32 reference, on logits of std ~1: each bf16
# rounding of the residual stream, q/k/v and FFN activations costs ~2^-9
# relative.  The same check in bf16 on a CPU at d_model 256 and 512 (two
# layers, 8 experts, 6 seeds) gave relative L2 0.012-0.027 and max abs
# 0.05-0.12; the bounds allow about twice the worst.  A wrong cache row,
# mask or routing bug errs by O(1).
LOGITS_REL_L2 = 6e-2
LOGITS_MAX_ABS = 0.25

COMPILE = {"seconds": 0.0, "count": 0, "cache_hits": 0}


def _on_duration(event, duration_secs, **kwargs):
    # wraps compile-or-load-from-cache: a cache hit costs only the load
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILE["seconds"] += duration_secs
        COMPILE["count"] += 1


def _on_event(event, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        COMPILE["cache_hits"] += 1


def check(ok: bool, what: str):
    print(("PASS " if ok else "FAIL ") + what, flush=True)
    if not ok:
        sys.exit(f"chip_smoke: check failed: {what}")


def compile_line(phase: str, t0: dict) -> str:
    return (f"{phase}: compile {COMPILE['seconds'] - t0['seconds']:.2f}s "
            f"({COMPILE['count'] - t0['count']} programs, "
            f"{COMPILE['cache_hits'] - t0['cache_hits']} from cache)")


def make_prompts(rng, n: int, vocab: int, lens: tuple, gens: tuple):
    plens = rng.integers(lens[0], lens[1] + 1, n)
    return ([rng.integers(0, vocab, int(k)).astype(np.int32) for k in plens],
            [int(g) for g in rng.integers(gens[0], gens[1] + 1, n)])


def paged_kernel_check(cfg, batch: int, m: int, mbs: int, block_size: int,
                       seed: int) -> float:
    """Max violation ratio |out - ref| / (atol + rtol |ref|) of the paged
    verify kernel at ``cfg``'s head widths, for ``batch`` sequences of up
    to ``mbs`` blocks each verifying ``m`` tokens; <= 1 passes."""
    from repro.kernels import ops, ref
    nb = 1 + batch * mbs
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    dt = jnp.dtype(cfg.dtype)
    q = jax.random.normal(kq, (batch, cfg.n_heads, m, cfg.head_dim), dt)
    pool = (nb, block_size, cfg.n_kv_heads, cfg.head_dim)
    kp = jax.random.normal(kk, pool, dt)
    vp = jax.random.normal(kv, pool, dt)
    bt = jnp.asarray(1 + rng.permutation(nb - 1).reshape(batch, mbs),
                     jnp.int32)
    lengths = jnp.asarray(rng.integers(m, mbs * block_size + 1, batch),
                          jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, bt, lengths)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(q.astype(jnp.float32), kp, vp,
                                              bt, lengths)
    err = jnp.abs(out.astype(jnp.float32) - want)
    bound = KERNEL_TOL["atol"] + KERNEL_TOL["rtol"] * jnp.abs(want)
    return float(jnp.max(err / bound))


def logits_check(params, cfg, prompt: np.ndarray, block_size: int) -> dict:
    """One prefill + one paged decode step through ``M.prefill`` /
    ``M.decode`` against :func:`reference_logits` of the same tokens.
    Returns the relative L2 and max abs errors over both logit rows."""
    from repro.models import model as M
    from repro.models.reference import reference_logits
    from repro.models.transformer import (admit_sequence_paged, init_cache,
                                          init_paged_cache)
    n = len(prompt)
    mbs = -(-(n + 1) // block_size)
    toks = jnp.asarray(prompt)[None, :]
    lg0, cache = jax.jit(M.prefill, static_argnums=1)(
        params, cfg, toks, init_cache(cfg, 1, mbs * block_size))
    paged = jax.jit(admit_sequence_paged, static_argnums=0)(
        cfg, init_paged_cache(cfg, 1, 1 + mbs, block_size, mbs), cache, 0,
        jnp.arange(1, mbs + 1, dtype=jnp.int32), n, 0)
    nxt = jnp.argmax(lg0, -1)
    lg1, _, _ = jax.jit(M.decode, static_argnums=1)(params, cfg, paged,
                                                    nxt[:, None])
    got = jnp.concatenate([lg0, lg1[:, 0]]).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference_logits(params, cfg,
                                jnp.concatenate([toks[0], nxt]))[n - 1:]
    diff = got - want
    return {"rel_l2": float(jnp.linalg.norm(diff) / jnp.linalg.norm(want)),
            "max_abs": float(jnp.max(jnp.abs(diff))),
            "logit_std": float(jnp.std(want))}


def serve_async(eng, prompts, gens):
    from repro.serving.server import AsyncServingServer

    async def drive():
        async with AsyncServingServer(eng, max_queue=8) as srv:
            hs = [await srv.submit(p, g, rid=1000 + i)
                  for i, (p, g) in enumerate(zip(prompts, gens))]
            outs = await asyncio.gather(*[srv.collect(h) for h in hs])
        return hs, outs

    return asyncio.run(asyncio.wait_for(drive(), timeout=600))


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return run(dev)


def run(dev) -> int:
    n_dev = len(jax.devices())
    print(f"device: {dev.platform} {dev.device_kind} x{n_dev}", flush=True)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.trace import poisson_requests

    print(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    print("memory kinds of device 0: "
          + ", ".join(sorted(m.kind for m in dev.addressable_memories())))

    # -- build
    args = serve.build_parser().parse_args(SERVE_ARGS)
    t0, c0 = time.perf_counter(), dict(COMPILE)
    eng = serve.build_engine(args)
    jax.block_until_ready((eng.engine.tp, eng.engine.dp))
    tcfg, dcfg = eng.target_cfg, eng.draft_cfg
    for role, c in (("target", tcfg), ("draft", dcfg)):
        print(f"{role}: {c.name} n_layers={c.n_layers} d_model={c.d_model} "
              f"d_ff={c.d_ff} heads={c.n_heads}/{c.n_kv_heads}x{c.head_dim} "
              f"experts={c.n_experts} top_k={c.top_k} vocab={c.vocab_size} "
              f"layers={c.layer_pattern} window={c.sliding_window} "
              f"dtype={c.dtype}")
    n_bytes = sum(x.nbytes for x in jax.tree.leaves((eng.engine.tp,
                                                     eng.engine.dp)))
    print(f"weights: {n_bytes / 1e9:.2f} GB; init "
          f"{time.perf_counter() - t0:.2f}s; " + compile_line("init", c0))

    # -- serve closed loop
    rng = np.random.default_rng(SEED)
    prompts, gens = make_prompts(rng, N_CLOSED, tcfg.vocab_size,
                                 (128, 512), (32, 64))
    reqs = poisson_requests(prompts, gens, RATE_RPS, seed=SEED)
    check(all([eng.submit(r) for r in reqs]),
          f"{len(reqs)} requests admitted to the queue")
    t0, c0 = time.perf_counter(), dict(COMPILE)
    done = eng.run()
    serve_s = time.perf_counter() - t0
    toks = sum(len(r.result) for r in done)
    print(f"closed loop: {len(done)} requests, {toks} tokens in "
          f"{serve_s:.2f}s (compile included); "
          + compile_line("serve", c0), flush=True)

    # -- serve async (fits the cache sized by the closed-loop queue)
    a_prompts, a_gens = make_prompts(rng, N_ASYNC, tcfg.vocab_size,
                                     (128, 256), (32, 32))
    t0, c0 = time.perf_counter(), dict(COMPILE)
    handles, streams = serve_async(eng, a_prompts, a_gens)
    print(f"async: {len(handles)} requests, "
          f"{sum(len(s) for s in streams)} streamed tokens in "
          f"{time.perf_counter() - t0:.2f}s; " + compile_line("async", c0))

    st = eng.stats()
    check(st["fused_compiles"] == 1,
          f"fused_compiles == 1 (got {st['fused_compiles']})")
    check(sorted(r.rid for r in done) == [r.rid for r in reqs]
          and all(len(r.result) == r.max_new_tokens for r in reqs),
          "every closed-loop request got its token count")
    check([len(s) for s in streams] == a_gens
          and all(list(h.result) == s for h, s in zip(handles, streams)),
          "every streamed request got its token count, as retired")

    # -- the compiled fused step holds the Pallas kernel
    t0, c0 = time.perf_counter(), dict(COMPILE)
    hlo = eng.lower_fused().compile().as_text()
    check("tpu_custom_call" in hlo,
          f"compiled fused step contains tpu_custom_call "
          f"({hlo.count('tpu_custom_call')} sites)")

    # -- kernel and model numerics
    kv = st["kv"]
    ratio = paged_kernel_check(
        tcfg, args.batch, args.n_cand + 1,
        (kv["num_blocks_per_half"] - 1) // args.batch, kv["block_size"],
        SEED)
    check(ratio <= 1.0, f"paged kernel vs kernels/ref.py within "
          f"atol={KERNEL_TOL['atol']} rtol={KERNEL_TOL['rtol']} "
          f"(worst |err|/bound = {ratio:.3f})")
    prompt = rng.integers(0, tcfg.vocab_size, 300).astype(np.int32)
    e = logits_check(eng.engine.tp, tcfg, prompt, kv["block_size"])
    check(e["rel_l2"] <= LOGITS_REL_L2 and e["max_abs"] <= LOGITS_MAX_ABS,
          f"prefill + paged decode logits vs float32 reference: rel L2 "
          f"{e['rel_l2']:.4f} <= {LOGITS_REL_L2}, max abs "
          f"{e['max_abs']:.4f} <= {LOGITS_MAX_ABS} (logit std "
          f"{e['logit_std']:.3f})")
    print(f"checks: {time.perf_counter() - t0:.2f}s; "
          + compile_line("checks", c0))

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", float("inf"))
    check(peak < PEAK_LIMIT, f"peak_bytes_in_use {peak / 1e9:.2f} GB < "
          f"{PEAK_LIMIT / 1e9:.0f} GB")
    print(f"compile seconds total: {COMPILE['seconds']:.2f} "
          f"({COMPILE['count']} programs, {COMPILE['cache_hits']} from "
          f"cache)")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
