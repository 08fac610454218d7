"""Plain float32 reference forward for the Mixtral / Mistral pairs.

Written from the models' published definitions (Mixtral of Experts,
arXiv:2401.04088; Mistral 7B, arXiv:2310.06825), independent of the
program: no scan, no cache, no kernels, no capacity dispatch, and nothing
imported from it.  It covers RMSNorm, GQA attention with rotate-half RoPE,
full or sliding-window causal masks, SwiGLU, and a softmax top-k mixture of
experts computed densely (every expert on every token, weighted by the
renormalised top-k gates, zero elsewhere).

Weights are read by name from the parameter tree the benchmark made
(``bench/weights.py``) and stay in their stored dtype; each matrix is
upcast only inside its own product, one layer and one expert at a time, so
the reference fits on the chip beside the weights.  All products run at
``precision="highest"``.

``quant="int8"`` is the control: every weight product is computed from
int8 values, the weight quantised per output column and the activation per
row (symmetric, absmax / 127), as an int8 serving path would.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s), s


def _mm(x, w, quant):
    w = w.astype(F32)
    if quant is None:
        return jnp.matmul(x, w, precision=HI)
    if quant != "int8":
        raise ValueError(f"unknown quant {quant!r}")
    xq, sx = _q8(x, -1)
    wq, sw = _q8(w, 0)
    return jnp.matmul(xq, wq, precision=HI) * sx * sw


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """Rotate-half RoPE over the last dim of (S, H, d)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg: dict, window, quant):
    s = x.shape[0]
    hq, hkv, d = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(s)
    q = _rope(_mm(x, p["wq"], quant).reshape(s, hq, d), pos,
              cfg["rope_theta"])
    k = _rope(_mm(x, p["wk"], quant).reshape(s, hkv, d), pos,
              cfg["rope_theta"])
    v = _mm(x, p["wv"], quant).reshape(s, hkv, d)
    # query head h reads kv head h // (hq // hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * d ** -0.5
    ok = pos[None, :] <= pos[:, None]
    if window is not None:
        ok &= pos[None, :] > pos[:, None] - window
    probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), -1)
    out = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return _mm(out.reshape(s, hq * d), p["wo"], quant)


def _swiglu(w_gate, w_up, w_down, x, quant):
    h = jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant)
    return _mm(h, w_down, quant)


def _moe(p, g, x, top_k, quant):
    probs = jax.nn.softmax(_mm(x, p["router"][g], quant), -1)   # (S, E)
    top, idx = jax.lax.top_k(probs, top_k)
    # how near each token is to another choice of experts: the gap
    # between the last chosen and the first unchosen gate
    margin = top[:, -1] - jax.lax.top_k(probs, top_k + 1)[0][:, -1]
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(
            top / top.sum(-1, keepdims=True))

    def one(acc, e):
        y = _swiglu(p["w_gate"][g, e], p["w_up"][g, e], p["w_down"][g, e],
                    x, quant)
        return acc + gates[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(probs.shape[-1]))
    return out, margin


@partial(jax.jit, static_argnames=("cfg_items", "kind", "quant"))
def _layer(p, x, g, *, cfg_items: tuple, kind: str, quant):
    """Layer ``g`` of the stacked layer params ``p``; returns the new
    residual stream and each token's routing margin (inf without MoE)."""
    cfg = dict(cfg_items)
    window = cfg["sliding_window"] if kind == "swa" else None
    attn = jax.tree.map(lambda a: a[g], p["attn"])
    x = x + _attention(attn, _rms(x, p["ln1"]["scale"][g]), cfg, window,
                       quant)
    h = _rms(x, p["ln2"]["scale"][g])
    f = p["ffn"]
    if cfg["n_experts"]:
        y, margin = _moe(f, g, h, cfg["top_k"], quant)
        return x + y, margin
    return (x + _swiglu(f["w_gate"][g], f["w_up"][g], f["w_down"][g], h,
                        quant), jnp.full(x.shape[:1], jnp.inf, F32))


@partial(jax.jit, static_argnames=("quant",))
def _head(embed, final_scale, x, *, quant):
    x = _rms(x, final_scale)
    head = embed.get("head")
    head = embed["tok"].T if head is None else head
    return _mm(x, head, quant)


def logits(params: dict, cfg: dict, tokens, quant=None):
    """Float32 next-token logits (S, V) for one token sequence (S,), the
    first token at position 0."""
    return logits_and_margins(params, cfg, tokens, quant)[0]


def logits_and_margins(params: dict, cfg: dict, tokens, quant=None):
    """Logits (S, V) and, per token, the smallest routing margin over the
    MoE layers (inf for a dense model)."""
    if cfg.get("norm", "rmsnorm") != "rmsnorm" or \
            cfg.get("activation", "swiglu") != "swiglu" or \
            any(k not in ("attn", "swa") for k in cfg["layer_pattern"]):
        raise NotImplementedError(f"the reference covers RMSNorm/SwiGLU "
                                  f"attention decoders, not {cfg['name']}")
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in cfg.items()))
    x = params["embed"]["tok"][jnp.asarray(tokens)].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    pattern = cfg["layer_pattern"]
    for g in range(cfg["n_layers"] // len(pattern)):
        for i, kind in enumerate(pattern):
            x, m = _layer(params["layers"][i], x, jnp.int32(g),
                          cfg_items=items, kind=kind, quant=quant)
            margin = jnp.minimum(margin, m)
    return _head(params["embed"], params["final_norm"]["scale"], x,
                 quant=quant), margin
