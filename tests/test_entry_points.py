"""Entry-point plumbing for running on a chip: the served pair at
published widths, the compile-cache rule, and the kernel routing switch."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import PAIRS
from repro.kernels import ops
from repro.launch import compile_cache, serve
from repro.serving.engine import SchedulerConfig, ServeRequest, ServingEngine

from conftest import tiny_config, tiny_draft_config

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def test_chip_smoke_serves_the_pair_at_published_widths():
    args = serve.build_parser().parse_args(chip_smoke.SERVE_ARGS)
    assert not args.reduced and args.length_bucket
    tcfg, dcfg = serve.model_pair(args.arch, args.reduced)
    assert (tcfg, dcfg) == PAIRS[args.arch]
    for c in (tcfg, dcfg):
        assert (c.d_model, c.d_ff, c.n_heads, c.n_kv_heads, c.head_dim,
                c.vocab_size, c.dtype) == (4096, 14336, 32, 8, 128, 32000,
                                           "bfloat16")
    assert (tcfg.n_experts, tcfg.top_k, tcfg.n_layers) == (8, 2, 2)
    assert (dcfg.sliding_window, dcfg.n_layers) == (4096, 4)


def test_serve_defaults_to_reduced():
    args = serve.build_parser().parse_args([])
    assert args.reduced
    tcfg, dcfg = serve.model_pair(args.arch, args.reduced)
    assert tcfg.dtype == "float32" and dcfg.vocab_size == tcfg.vocab_size


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_stands(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_kernel_routing_switch():
    assert ops.use_compiled_kernels() == (jax.default_backend() == "tpu")
    with ops.compiled_kernels(True):
        assert ops.use_compiled_kernels()
        with ops.compiled_kernels(False):
            assert not ops.use_compiled_kernels()
        assert ops.use_compiled_kernels()
    assert ops.use_compiled_kernels() == (jax.default_backend() == "tpu")


def test_importing_kernels_starts_no_backend():
    code = ("import repro.kernels.ops, repro.models.attention\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": str(ROOT / "src")})
    assert r.returncode == 0, r.stderr[-2000:]


def test_chip_smoke_refuses_a_cpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and '"ok"' not in r.stdout


def test_lower_fused_inspects_the_served_step_without_retracing():
    eng = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                        config=SchedulerConfig(max_batch=2, n_cand=2))
    with pytest.raises(ValueError):
        eng.lower_fused()
    eng.init_from_seed(0)
    for i in range(3):
        eng.submit(ServeRequest(i, np.arange(4 + i, dtype=np.int32), 5))
    eng.run()
    hlo = eng.lower_fused().compile().as_text()
    assert "tpu_custom_call" not in hlo         # CPU: reference gather
    assert eng.stats()["fused_compiles"] == 1
