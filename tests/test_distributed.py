"""Distributed-correctness tests (run in subprocesses with 8 fake devices,
since the main pytest process holds the 1-device CPU backend)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(body: str):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                            "HOME": os.environ.get("HOME", ""),
                            "JAX_PLATFORMS": "cpu"},
                       timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


def test_moe_distributed_modes_match_local():
    out = _run("""
        from repro.models.moe import (apply_moe, init_moe, _moe_local,
                                      select_moe_mode)
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        E, D, F, topk = 8, 64, 96, 2
        p = init_moe(jax.random.PRNGKey(0), D, F, E, "swiglu", jnp.float32)
        for b, s, expect in [(4, 8, "ep"), (6, 1, "ep_psum")]:
            x = jax.random.normal(jax.random.PRNGKey(1), (b, s, D))
            ref = _moe_local(p, x.reshape(-1, D), n_experts=E, top_k=topk,
                             capacity_factor=float("inf"),
                             activation="swiglu").reshape(b, s, D)
            with jax.set_mesh(mesh):
                mode = select_moe_mode(E, s, mesh)
                assert mode == expect, (mode, expect)
                out = jax.jit(lambda pp, xx: apply_moe(
                    pp, xx, n_experts=E, top_k=topk, activation="swiglu",
                    mesh=mesh, capacity_factor=float("inf")))(p, x)
            err = float(jnp.abs(out - ref).max())
            assert err < 1e-5, (mode, err)
        print("MOE_OK")
    """)
    assert "MOE_OK" in out


def test_sharded_decode_matches_single_device():
    """decode_step under a (2,4) mesh == decode_step on one device,
    including the weight-stationary decode hints."""
    out = _run("""
        from repro.configs.base import ModelConfig
        from repro.models import model as M
        from repro.models.transformer import init_cache
        cfg = ModelConfig(name="t", arch_type="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                          vocab_size=97, dtype="float32", remat=False)
        p = M.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 97)
        cache = init_cache(cfg, 4, 24)
        lg, cache = M.prefill(p, cfg, toks, cache)
        nxt = jnp.argmax(lg, -1)[:, None]
        ref, _ = M.decode_step(p, cfg, cache, nxt)

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            lg2, cache2 = jax.jit(M.prefill, static_argnums=(1,))(
                p, cfg, toks, init_cache(cfg, 4, 24))
            got, _ = jax.jit(M.decode_step, static_argnums=(1,))(
                p, cfg, cache2, nxt)
        err = float(jnp.abs(ref - got).max())
        assert err < 1e-4, err
        print("DECODE_OK")
    """)
    assert "DECODE_OK" in out


def test_train_step_runs_under_mesh():
    """One real (tiny) train step executes under the production-style mesh
    with the sequence-parallel profile + grad accumulation."""
    out = _run("""
        from repro.configs.base import ModelConfig
        from repro.models import model as M
        from repro.models.layers import sequence_sharding
        from repro.training.optimizer import make_optimizer
        from repro.training.train_loop import make_train_step
        cfg = ModelConfig(name="t", arch_type="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                          vocab_size=97, dtype="float32", remat=True)
        p = M.init_params(cfg, jax.random.PRNGKey(0))
        oi, _ = make_optimizer("adamw")
        st = oi(p)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (8, 32), 0, 97)}
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        step = make_train_step(cfg, mesh, 1e-3, accum_steps=2)
        with jax.set_mesh(mesh):
            def fn(pp, ss, bb):
                with sequence_sharding("model"):
                    return step(pp, ss, bb)
            p2, st2, loss = jax.jit(fn)(p, st, batch)
        assert bool(jnp.isfinite(loss)), loss
        print("TRAIN_OK", float(loss))
    """)
    assert "TRAIN_OK" in out
