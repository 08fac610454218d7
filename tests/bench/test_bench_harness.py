"""The benchmark harness on the CPU at a tiny pair: traffic generation, the
device check of bench/run.py, the result line's shape, and ``correct``
turning false when the timed path is broken (bench/harness.py,
bench/check.py)."""
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402

TARGET = dict(name="tiny-moe", arch_type="moe", n_layers=2, d_model=64,
              n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
              vocab_size=128, layer_pattern=["attn"], sliding_window=64,
              n_experts=4, top_k=2, moe_dropless=True, rope_theta=1e6,
              norm="rmsnorm", activation="swiglu", tie_embeddings=False,
              dtype="float32", kv_cache_dtype="float32")
DRAFT = dict(TARGET, name="tiny-draft", arch_type="dense", n_experts=0,
             top_k=0, layer_pattern=["swa"], sliding_window=32,
             rope_theta=1e4)
# float32 program against the float32 reference: readings are rounding
DOC = {"name": "tiny-pair", "target": TARGET, "draft": DRAFT,
       "limits": {"default": {"target_settled_gap": 1e-3, "draft_gap": 1e-3}}}
MIX = {"kind": "closed", "outstanding_per_max_batch": 4, "n_requests": 64,
       "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 8, "max": 32},
       "output_len": {"dist": "uniform", "min": 8, "max": 24},
       "serving": {"max_batch": 2, "n_cand": 2, "length_bucket": 16}}
CELL = "tiny.offline"
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


def _bench():
    b = harness.load_benchmark()
    b["workloads"].append({"name": CELL, "config": "tiny-pair",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if "tokens_per_s" == m["name"]:
            m["workloads"].append(CELL)
    return b


def _run(fault=None, control=False):
    return harness.run_cell(CELL, 2**31 + 17, 1.0, False,
                            t_process0=time.monotonic(), bench=_bench(),
                            doc=DOC, mix=MIX, fault=fault, control=control,
                            compile_cache=False)


def test_traffic_same_for_a_seed_and_reordered_across_seeds():
    mix = traffic.load_mix("offline")
    a = traffic.generate(mix, 2**33 + 5, 32000)
    b = traffic.generate(mix, 2**33 + 5, 32000)
    c = traffic.generate(mix, 2**33 + 6, 32000)
    key = lambda rs: [(r.prompt.tolist(), r.max_new_tokens) for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # the same sizes, in another order
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in c)
    assert len(a) == 512
    assert all(128 <= len(r.prompt) <= 1024 for r in a)
    assert all(128 <= r.max_new_tokens <= 512 for r in a)
    assert all(1 <= t < 32000 for r in a for t in r.prompt)


def test_unknown_traffic_kind_raises():
    with pytest.raises(ValueError, match="unknown traffic kind"):
        traffic.generate(dict(MIX, kind="open"), 1, 128)


def test_padding_matches_the_length_bucket():
    p = np.arange(1, 21, dtype=np.int32)
    out = traffic.padded(p, 16)
    assert len(out) == 32 and (out[:12] == 0).all() and \
        (out[12:] == p).all()


def test_benchmark_json_names_files_that_exist():
    b = harness.load_benchmark()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for c in b["configs"]:
        assert name.match(c["name"]) and (ROOT / c["file"]).exists()
    for w in b["workloads"]:
        assert name.match(w["name"]) and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for w in b["workloads"]:
        assert harness.cell_metrics(b, w, False)
        assert harness.cell_metrics(b, w, True)


def test_run_py_exits_nonzero_without_a_tpu():
    name = harness.load_benchmark()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        name, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 1, p.stderr
    assert "needs a TPU" in p.stderr
    assert p.stdout == ""


def test_last_line_has_the_contract_keys():
    r = _run()
    assert list(r) == CONTRACT_KEYS          # checks comes last
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in r["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(r["device"])
    assert all(set(c) == {"value", "limit"} for c in r["checks"].values())
    json.dumps(r)


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "token_altered", "draft_altered"])
def test_a_broken_timed_path_is_not_correct(fault):
    r = _run(fault=fault)
    assert r["correct"] is False, (fault, r["checks"])


def test_int8_control_fails_the_limits():
    r = _run(control=True)
    assert r["correct"] is True, r["checks"]
    # the int8 reference in the program's place, judged by the same rule
    # and limits, is not correct: it fails one of the numbers, not each
    assert r["control_correct"] is False, r["control"]
    assert set(r["control"]) == set(r["checks"])
    assert any(c["value"] > c["limit"] for c in r["control"].values())
