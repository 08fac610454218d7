"""Run one cell of ``BENCHMARK.json`` once and build its result line.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); its metrics are read by
``bench/metrics/<metric>.py``, each a module with ``read(ctx)`` that
returns a number or None (nothing to read here).  Adding a cell, a mix, a
configuration or a metric adds files and entries; no file here changes.

One run: build ``ServingEngine`` with weights made from the seed
(``bench/weights.py``), warm up every shape the mix can draw, open the
window, drive ``ServingEngine.run_step`` with the mix for ``seconds``, and
then, with the program's state freed, compare a sample of what the window
served with the plain reference (``bench/check.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
OUT_DIR = ROOT / "bench_out"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what the cell is


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def cell(bench: dict, name: str) -> tuple:
    """(workload entry, configuration document, traffic mix) of cell
    ``name``."""
    from bench import traffic
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    doc = json.loads((ROOT / conf["file"]).read_text())
    return w, doc, traffic.load_mix(w["traffic"])


def cell_metrics(bench: dict, w: dict, trace: bool) -> list:
    """The metric entries a run of cell ``w`` reports: end-to-end ones
    with ``--trace 0``, per-layer ones with ``--trace 1``."""
    e2e = [m for m in bench["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (w["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_configs(doc: dict):
    from repro.configs.base import ModelConfig
    mk = lambda d: ModelConfig(**{**d, "layer_pattern":
                                  tuple(d["layer_pattern"])})
    return mk(doc["target"]), mk(doc["draft"])


def limits_for(doc: dict, traffic_name: str) -> dict:
    lim = doc.get("limits", {})
    return lim.get(traffic_name, lim.get("default", {}))


def prompt_buckets(mix: dict) -> list:
    b = mix["serving"]["length_bucket"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    return list(range(b * math.ceil(lo / b), b * math.ceil(hi / b) + 1, b))


# ---------------------------------------------------------------------------
# watching the timed path from outside


@dataclasses.dataclass
class Round:
    t0: float
    t1: float
    drafts: object            # device (B, n_cand) drafts this round verified
    tokens: np.ndarray        # RoundOutput.tokens
    n_emitted: np.ndarray
    live: dict                # rid -> tokens served before the round
    emits: list = dataclasses.field(default_factory=list)  # (rid, k, tok)


class Observer:
    """Records what reaches the host: every token with its time (the
    engine's ``emit_hook``/``finish_hook``), and per fused round the drafts
    it verified and its outputs (a wrapper of the public
    ``SpecOffloadEngine.decode_round``).  ``fault`` breaks the timed path
    on purpose; the tests use it to see ``correct`` turn false."""

    def __init__(self, eng, vocab: int, fault: str | None = None):
        self.tokens, self.times, self.finished = {}, {}, {}
        self.rounds: list = []
        self.fault, self.vocab = fault, vocab
        self.live: dict = {}
        eng.emit_hook = self._emit
        eng.finish_hook = self._finish
        core = eng.engine
        self._orig = core.decode_round
        core.decode_round = self._decode_round

    def _emit(self, req, tok):
        t = time.monotonic()
        toks = self.tokens.setdefault(req.rid, [])
        if toks and self.rounds:
            self.rounds[-1].emits.append((req.rid, len(toks), int(tok)))
        toks.append(int(tok))
        self.times.setdefault(req.rid, []).append(t)
        self.live[req.rid] = len(toks)

    def _finish(self, req):
        self.finished[req.rid] = time.monotonic()
        self.live.pop(req.rid, None)

    def _decode_round(self, verify, gen, n_cand, record=True, tree=None):
        drafts = verify.drafts
        live = dict(self.live)
        if self.fault == "stale_state":
            before = (verify.target_cache, verify.draft_cache)
        t0 = time.monotonic()
        out = self._orig(verify, gen, n_cand, record=record, tree=tree)
        if self.fault == "stale_state":
            verify.target_cache, verify.draft_cache = before
        elif self.fault == "half_batch":
            out.tokens = np.array(out.tokens)
            out.tokens[out.tokens.shape[0] // 2:] = 0
        elif self.fault == "token_altered":
            out.tokens = np.array(out.tokens)
            i = len(self.rounds) % out.tokens.shape[0]
            out.tokens[i, 0] = (out.tokens[i, 0] + 1) % self.vocab
        elif self.fault == "draft_altered" and gen.drafts is not None:
            gen.drafts = (gen.drafts + 1) % self.vocab
        self.rounds.append(Round(t0, time.monotonic(), drafts, out.tokens,
                                 out.n_emitted, live))
        return out


def match_slot(rnd: Round, emitted: list) -> int | None:
    """The slot whose output row is exactly ``emitted`` this round, when
    one slot alone fits; None when none or several do."""
    n = len(emitted)
    hits = [i for i in range(rnd.tokens.shape[0])
            if int(rnd.n_emitted[i]) >= n
            and list(rnd.tokens[i, :n]) == emitted]
    return hits[0] if len(hits) == 1 else None


# ---------------------------------------------------------------------------
# one run


class CompileCounter:
    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


def counters(eng) -> dict:
    """Each counter of the engine's registry, totalled over its label
    sets."""
    snap = eng.metrics()["metrics"]
    return {name: sum(series.values())
            for name, series in snap["counters"].items()}


def build_engine(doc: dict, mix: dict, seed: int, trace: bool):
    """``ServingEngine`` for configuration ``doc`` with the serving
    parameters of ``mix`` and weights made from ``seed``; returns
    (engine, target params, draft params)."""
    import jax

    from bench import traffic, weights
    from repro.core.pipeline import required_cache_len
    from repro.models import model as M
    from repro.serving.engine import SchedulerConfig, ServingEngine
    from repro.sim.hardware import ENVS

    tcfg, dcfg = model_configs(doc)
    sv = mix["serving"]
    n_cand, bucket = sv["n_cand"], sv["length_bucket"]
    pmax, omax = traffic.max_sizes(mix)
    max_len = required_cache_len(bucket * math.ceil(pmax / bucket), omax,
                                 n_cand)
    eng = ServingEngine(tcfg, dcfg, ENVS["v5e"], config=SchedulerConfig(
        max_batch=sv["max_batch"], n_cand=n_cand, length_bucket=bucket,
        eos_id=-1, clock="real", max_len=max_len, trace=trace,
        trace_fence=False, trace_annotations=trace))
    t = time.monotonic()
    tp, dp = weights.build(M.init_params, tcfg, dcfg, seed)
    jax.block_until_ready((tp, dp))
    eng.load(tp, dp)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves((tp, dp)))
    log(f"weights: {n_bytes / 1e9:.3f} GB in {time.monotonic() - t:.2f}s")
    return eng, tp, dp


def warm_up(eng, mix: dict, seed: int):
    """Serve one short request per prompt bucket the mix can draw, with
    prompts from another stream than the window's, so that every program
    the window runs is compiled or loaded before it opens."""
    from repro.serving.engine import ServeRequest
    rng = np.random.default_rng([seed, 1])
    n_out = 2 * (mix["serving"]["n_cand"] + 1) + 1
    for i, n in enumerate(prompt_buckets(mix)):
        eng.submit(ServeRequest(
            rid=-1 - i, max_new_tokens=n_out,
            prompt=rng.integers(1, eng.target_cfg.vocab_size,
                                n).astype(np.int32)))
    while eng.has_work():
        eng.run_step()


class Client:
    """Submits the mix's requests, each stamped with the engine's clock at
    its submission, and keeps those the engine turned away."""

    def __init__(self, eng):
        self.eng = eng
        self.submitted, self.rejected = {}, []

    def submit(self, r):
        from repro.serving.engine import ServeRequest
        sr = ServeRequest(rid=r.rid, prompt=r.prompt,
                          max_new_tokens=r.max_new_tokens,
                          arrival_s=self.eng.now())
        self.submitted[r.rid] = sr
        if not self.eng.submit(sr):
            self.rejected.append(r.rid)


class HostClock:
    """What the host did during the window: the process's CPU seconds and
    Python's garbage-collection pauses (count, seconds, longest)."""

    def __init__(self):
        self.n_gc, self.gc_s, self.gc_max = 0, 0.0, 0.0
        self._t = None
        self.cpu0 = time.process_time()
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            d = time.monotonic() - self._t
            self.n_gc, self.gc_s = self.n_gc + 1, self.gc_s + d
            self.gc_max = max(self.gc_max, d)

    def stop(self) -> str:
        gc.callbacks.remove(self._on_gc)
        return (f"host in window: cpu {time.process_time() - self.cpu0:.3f}s"
                f"; {self.n_gc} gc pauses, {self.gc_s * 1e3:.1f} ms in all, "
                f"longest {self.gc_max * 1e3:.1f} ms")


def round_timing(rounds: list, w0: float, w1: float) -> str:
    """Where the window's wall time went, round by round: the interval
    from one fused round's start to the next, the part inside
    ``decode_round`` (dispatch and waiting on the device) and the host
    work between rounds, as median / 99th percentile / largest in ms; and
    the rounds that took over four times the median inside
    ``decode_round`` (stalls), with when the longest began."""
    rs = [r for r in rounds if w0 <= r.t0 and r.t1 <= w1]
    if len(rs) < 2:
        return "round timing: too few rounds"
    t0 = np.array([r.t0 for r in rs])
    t1 = np.array([r.t1 for r in rs])
    parts = {"interval": np.diff(t0), "in_round": t1 - t0,
             "between": t0[1:] - t1[:-1]}
    inr = parts["in_round"]
    stall = inr > 4 * np.median(inr)
    top = np.argsort(-inr)[:3]
    return ("round timing (ms, p50/p99/max): " + "; ".join(
        f"{k} {np.percentile(v, 50) * 1e3:.2f}/"
        f"{np.percentile(v, 99) * 1e3:.2f}/{v.max() * 1e3:.2f}"
        for k, v in parts.items())
        + f"; {int(stall.sum())} stalls, {inr[stall].sum():.3f}s in all; "
        "longest at " + ", ".join(f"{t0[i] - w0:.1f}s ({inr[i] * 1e3:.0f} ms)"
                                  for i in top))


def decide(checks: dict) -> bool:
    """``correct``: every number compared is there and within its
    limit."""
    return all(c["limit"] is not None and c["value"] is not None
               and c["value"] <= c["limit"] for c in checks.values())


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process0: float, bench: dict | None = None,
             doc: dict | None = None, mix: dict | None = None,
             fault: str | None = None, control: bool = False,
             compile_cache: bool = True) -> dict:
    """One run of cell ``workload``; returns the result line's object.
    ``doc``/``mix`` stand in for the cell's files (the tests pass tiny
    ones); ``control`` also reads the int8 reference at the checked
    positions."""
    import jax

    from bench import check, peaks, traffic, xplane
    from repro.launch.compile_cache import enable_compile_cache

    bench = load_benchmark() if bench is None else bench
    if doc is None or mix is None:
        w, doc, mix = cell(bench, workload)
    else:
        w = {x["name"]: x for x in bench["workloads"]}[workload]
    metrics = cell_metrics(bench, w, trace)
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    log(f"device: {dev.platform} {dev.device_kind} x{n_dev}")
    if compile_cache:
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    eng, tp, dp = build_engine(doc, mix, seed, trace)
    sv, vocab = mix["serving"], eng.target_cfg.vocab_size
    bucket = sv["length_bucket"]
    t = time.monotonic()
    warm_up(eng, mix, seed)
    log(f"warm-up: {time.monotonic() - t:.2f}s, {compiles.n} compiles")

    obs = Observer(eng, vocab, fault)
    reqs = traffic.generate(mix, seed, vocab)
    by_rid = {r.rid: r for r in reqs}
    client = Client(eng)
    # fill every slot before the window: the window sees the steady state
    # of a full batch with a backlog behind it
    outstanding = mix["outstanding_per_max_batch"] * sv["max_batch"]
    for r in reqs[:outstanding]:
        client.submit(r)
    nxt = outstanding
    while len(obs.live) < min(2 * sv["max_batch"], outstanding):
        for _ in eng.run_step():
            if nxt < len(reqs):
                client.submit(reqs[nxt])
                nxt += 1

    c0, n_comp0 = counters(eng), compiles.n
    trace_dir = OUT_DIR / "trace" / w["name"]
    t_span = (seconds / 3, seconds / 3 + min(5.0, seconds / 3))
    traced, note = [], []
    annot = (lambda name: jax.profiler.TraceAnnotation(name)) if trace \
        else (lambda name: nullcontext())

    def tick(now):
        """Start, then stop, the profiler over part of the window."""
        if not trace or len(traced) == 2:
            return
        if not traced and now >= w0 + t_span[0]:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            # an annotation made before the profiler starts records nothing
            note.append(jax.profiler.TraceAnnotation("bench/traced_window"))
            note[0].__enter__()
            traced.append(time.monotonic())
        elif traced and (now is None or now >= w0 + t_span[1]):
            note[0].__exit__(None, None, None)
            traced.append(time.monotonic())
            jax.profiler.stop_trace()

    host = HostClock()
    w0 = time.monotonic()
    setup_s = w0 - t_process0
    w_end = w0 + seconds
    while (now := time.monotonic()) < w_end:
        tick(now)
        with annot("bench/run_step"):
            done = eng.run_step()
        for _ in done:
            if nxt < len(reqs):
                client.submit(reqs[nxt])
                nxt += 1
    w1 = time.monotonic()
    host_line = host.stop()
    if len(traced) == 1:
        tick(None)
    c1, n_comp = counters(eng), compiles.n - n_comp0
    log(f"window: {w1 - w0:.3f}s, {len(obs.rounds)} rounds, compiles in "
        f"window {n_comp}")
    log(round_timing(obs.rounds, w0, w1))
    log(host_line)
    stats = eng.stats()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak}; fused_compiles {stats['fused_compiles']}"
        f"; mean occupancy {stats['mean_occupancy']:.3f}")

    # -- per-layer inputs from the traced sub-window
    reduced, rounds_traced = None, []
    if len(traced) == 2:
        tr = xplane.load(xplane.find_xplane(trace_dir))
        span = [(s, e) for n, s, e in tr.host if n == "bench/traced_window"]
        reduced = xplane.reduce(tr, span[0] if span else None)
        rounds_traced = [r for r in obs.rounds
                         if traced[0] <= r.t0 and r.t1 <= traced[1]]
    emits = [t for ts in obs.times.values() for t in ts]
    ctx = {"cell": w["name"], "config": doc, "mix": mix,
           "window_s": w1 - w0, "setup_s": setup_s,
           "tokens_in_window": sum(1 for t in emits if w0 <= t <= w1),
           "counters": (c0, c1), "trace": reduced, "rounds": rounds_traced,
           "padded_len": {rid: len(traffic.padded(by_rid[rid].prompt,
                                                  bucket))
                          for rid in by_rid},
           "device_kind": dev.device_kind}
    if trace or any(m["source"] == "device_trace" for m in metrics):
        ctx["peaks"] = peaks.peaks(dev.device_kind)
    values = {}
    for m in metrics:
        v = reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log(f"metric {m['name']}: {v}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev, "memory_peak_bytes": peak}
    result = {"attempted": len(client.submitted),
              "failed": len(client.rejected), "metrics": values,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_ns / 1e9
        device["window_s"] = reduced.window_ns / 1e9
        result["breakdown"] = xplane.breakdown(reduced)
        log(f"trace: busy {device['busy_s']:.4f}s of "
            f"{device['window_s']:.4f}s; longest gaps "
            f"{reduced.longest_gaps[:5]}")

    # -- correctness, with the program's state freed
    finished = [rid for rid, t in obs.finished.items()
                if rid in by_rid and t <= w1]
    samples = check.sample(finished, obs, by_rid, bucket, seed)
    client.eng = None
    del eng, stats
    gc.collect()
    t = time.monotonic()
    readings = check.compare(tp, dp, doc, samples, check.seq_len(mix),
                             control=control)
    log(f"reference check: {time.monotonic() - t:.2f}s over "
        f"{len(samples)} requests, "
        f"{sum(len(s['served']) for s in samples)} served tokens")
    check.log_readings(readings, log)
    lim = limits_for(doc, w.get("traffic", ""))
    short = {"value": sum(1 for rid in finished
                          if len(obs.tokens[rid])
                          != by_rid[rid].max_new_tokens), "limit": 0}
    checks = {"short_requests": short}
    for k, v in lim.items():
        checks[k] = {"value": readings.get(k), "limit": v}
    if control:
        # the int8 reference in the program's place, judged by the same
        # limits and the same rule as the program
        ctrl = {"short_requests": short}
        for k, v in lim.items():
            ctrl[k] = {"value": readings.get("control_" + k), "limit": v}
        result["control_correct"] = bool(samples) and decide(ctrl)
        result["control"] = ctrl
        result["arrays"] = readings["arrays"]
        for k, c in ctrl.items():
            log(f"control check {k}: {c['value']} limit {c['limit']}")
    correct = bool(samples) and decide(checks)
    for k, c in checks.items():
        log(f"check {k}: {c['value']} limit {c['limit']}")
    return {"correct": correct, **result, "checks": checks}
