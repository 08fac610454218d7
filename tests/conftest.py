"""Shared fixtures/utilities for the test suite.

NOTE: no XLA_FLAGS device-count override here — smoke tests and benches see
the single real CPU device.  The multi-pod dry-run sets its own flags (and
runs as a subprocess in tests that need many devices).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.models import model as M


def tiny_config(pattern=("attn",), arch="dense", n_layers=None, **kw):
    return ModelConfig(
        name="tiny", arch_type=arch,
        n_layers=n_layers or (len(pattern) * 2),
        d_model=kw.pop("d_model", 64), n_heads=kw.pop("n_heads", 4),
        n_kv_heads=kw.pop("n_kv_heads", 2), d_ff=kw.pop("d_ff", 128),
        vocab_size=kw.pop("vocab_size", 61), layer_pattern=pattern,
        sliding_window=kw.pop("sliding_window", 8),
        dtype="float32", remat=False, **kw)


def tiny_draft_config(vocab_size=61):
    return ModelConfig(
        name="tiny-draft", arch_type="dense", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=vocab_size,
        layer_pattern=("swa",), sliding_window=8, dtype="float32",
        remat=False)


def tiny_mla_pair() -> tuple:
    """ModelConfig keyword dicts of a tiny Kimi-K2-shaped target (MLA with
    q-LoRA and YaRN rotary, a leading dense layer, a shared expert, a
    sigmoid-routed share: experts 2..5 of the router's 8) and a tiny
    Moonlight-shaped draft (no q-LoRA, plain rotary, all 8 experts held,
    two shared), float32."""
    target = dict(
        name="tiny-k2", arch_type="moe", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=32, d_ff=96, vocab_size=128,
        layer_pattern=["mla"], n_experts=4, router_experts=8,
        expert_offset=2, top_k=3, expert_d_ff=32, n_shared_experts=1,
        router="sigmoid_bias",
        routed_scaling_factor=2.827, first_dense_layers=1, moe_dropless=True,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, rope_theta=50000.0,
        rope_scaling={"type": "yarn", "factor": 32,
                      "original_max_position_embeddings": 4096,
                      "beta_fast": 1, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1},
        norm="rmsnorm", norm_eps=1e-6, activation="swiglu",
        tie_embeddings=False, dtype="float32", kv_cache_dtype="float32")
    draft = dict(target, name="tiny-moonlight", n_experts=8,
                 router_experts=8, expert_offset=0, top_k=2,
                 n_shared_experts=2, q_lora_rank=0, rope_scaling={},
                 norm_eps=1e-5, routed_scaling_factor=2.446)
    return target, draft


def model_config(doc: dict) -> ModelConfig:
    """A ModelConfig from a JSON-style dict (lists for tuples)."""
    return ModelConfig(**{**doc, "layer_pattern": tuple(doc["layer_pattern"])})


@pytest.fixture(scope="session")
def jitted():
    """Jitted model entry points (cfg/mesh static)."""
    return {
        "forward_train": jax.jit(M.forward_train, static_argnums=(1,),
                                 static_argnames=("mesh",)),
        "prefill": jax.jit(M.prefill, static_argnums=(1,),
                           static_argnames=("mesh",)),
        "decode_step": jax.jit(M.decode_step, static_argnums=(1,),
                               static_argnames=("mesh",)),
        "decode": jax.jit(M.decode, static_argnums=(1,),
                          static_argnames=("mesh",)),
        "commit": jax.jit(M.commit, static_argnums=(0, 4)),
    }


def greedy_reference(params, cfg, toks, steps, maxlen, jitted):
    """Pure greedy decoding reference: returns (steps,) tokens per seq."""
    from repro.models.transformer import init_cache
    b = toks.shape[0]
    cache = init_cache(cfg, b, maxlen)
    lg, cache = jitted["prefill"](params, cfg, toks, cache)
    out = []
    tok = jnp.argmax(lg, -1)
    for _ in range(steps):
        out.append(tok)
        lg, cache = jitted["decode_step"](params, cfg, cache, tok[:, None])
        tok = jnp.argmax(lg, -1)
    return jnp.stack(out, axis=1)
