"""Plain float32 reference forward for attention decoders.

Written from the model's definition, not from its code: no scan, no
cache, no kernels, no capacity dispatch.  It covers what the served
Mixtral / Mistral configs use: full and sliding-window GQA attention with
RoPE, RMSNorm, SwiGLU, and a top-k mixture of experts computed densely
(every expert on every token, weighted by the renormalized top-k gates,
zero elsewhere).  Weights stay in their stored dtype and are upcast one
layer (one expert) at a time, so the reference fits beside the model on
one chip.  Callers wanting exact float32 matmuls wrap the call in
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, SWA, ModelConfig

F32 = jnp.float32


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """Rotate-half RoPE over the last dim of (S, H, d)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs            # (S, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg: ModelConfig, window):
    s = x.shape[0]
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(s)
    q = _rope((x @ p["wq"].astype(F32)).reshape(s, hq, d), pos,
              cfg.rope_theta)
    k = _rope((x @ p["wk"].astype(F32)).reshape(s, hkv, d), pos,
              cfg.rope_theta)
    v = (x @ p["wv"].astype(F32)).reshape(s, hkv, d)
    # query head h reads kv head h // (hq // hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    ok = pos[None, :] <= pos[:, None]
    if window is not None:
        ok &= pos[None, :] > pos[:, None] - window
    probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), -1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, hq * d)
    return out @ p["wo"].astype(F32)


def _swiglu(w_gate, w_up, w_down, x):
    h = jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))
    return h @ w_down.astype(F32)


def _moe(p, g, x, top_k):
    probs = jax.nn.softmax(x @ p["router"][g].astype(F32), -1)  # (S, E)
    top, idx = jax.lax.top_k(probs, top_k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(
            top / top.sum(-1, keepdims=True))
    # one expert at a time: only that expert's weights are upcast
    outs = jax.lax.map(
        lambda e: _swiglu(p["w_gate"][g, e], p["w_up"][g, e],
                          p["w_down"][g, e], x),
        jnp.arange(probs.shape[-1]))                            # (E, S, D)
    return jnp.einsum("se,esd->sd", gates, outs)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _layer(p, g: int, cfg: ModelConfig, kind: str, moe: bool, x):
    """Layer group ``g`` of the stacked params ``p`` (sliced in here, so
    no group's weights are copied out whole)."""
    window = cfg.sliding_window if kind == SWA else None
    attn = jax.tree.map(lambda a: a[g], p["attn"])
    x = x + _attention(attn, _rms(x, p["ln1"]["scale"][g]), cfg, window)
    h = _rms(x, p["ln2"]["scale"][g])
    f = p["ffn"]
    if moe:
        return x + _moe(f, g, h, cfg.top_k)
    return x + _swiglu(f["w_gate"][g], f["w_up"][g], f["w_down"][g], h)


def reference_logits(params: dict, cfg: ModelConfig,
                     tokens: jax.Array) -> jax.Array:
    """Float32 next-token logits (S, V) for one token sequence (S,)."""
    if cfg.norm != "rmsnorm" or cfg.activation != "swiglu" or \
            any(k not in (ATTN, SWA) for k in cfg.layer_pattern):
        raise NotImplementedError(f"reference covers RMSNorm/SwiGLU "
                                  f"attention decoders, not {cfg.name}")
    x = params["embed"]["tok"][tokens].astype(F32)
    for g in range(cfg.n_groups):
        for i, kind in enumerate(cfg.layer_pattern):
            x = _layer(params["layers"][i], g, cfg, kind,
                       bool(cfg.is_moe and cfg.moe_pattern[i]), x)
    x = _rms(x, params["final_norm"]["scale"])
    head = params["embed"].get("head")
    head = params["embed"]["tok"].T if head is None else head
    return x @ head.astype(F32)
