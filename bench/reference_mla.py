"""Plain float32 reference forward for the Kimi-K2 / Moonlight pair: the
DeepSeek-V3 block with multi-head latent attention.

Written from the published definitions (DeepSeek-V2 §2.1,
arXiv:2405.04434, for MLA; DeepSeek-V3, arXiv:2412.19437, for sigmoid
``noaux_tc`` routing; the YaRN rotary of the ``modeling_deepseek.py``
shipped beside the configs), independent of the program: no scan, no
cache, no kernels, no capacity dispatch, no absorbed attention, and
nothing imported from it.  Per layer:

* attention (MLA, not absorbed): ``q = RMSNorm(x wq_a) wq_b`` (or ``x
  wq``), split per head into a no-rope and a rope part; ``[c_kv, k_pe] =
  x wkv_a``; per-head keys ``[RMSNorm(c_kv) W_UK ‖ k_pe]`` and values
  ``RMSNorm(c_kv) W_UV`` through ``wkv_b``; causal softmax attention at
  scale ``(nope + rope) ** -0.5 * mscale ** 2``; rotary on interleaved
  pairs, with YaRN frequencies where the config has ``rope_scaling``;
* FFN: a SwiGLU MLP in the leading dense layers; elsewhere sigmoid
  scores over the router's ``router_experts``, the top-k chosen on score
  plus the correction bias, gates the chosen unbiased scores over their
  sum times ``routed_scaling_factor``; every expert held here
  (``expert_offset`` .. ``+ n_experts``) computed on every token and
  weighted by its gate (zero where not chosen); the shared experts added.
  Experts the layer does not hold add nothing, as in the program.

A token's routing margin is, over the selection scores (score + bias)
across all the router's experts, the last chosen minus the first
unchosen: how near the token is to another choice.

Weights are read by name from the parameter tree the benchmark made
(``bench/weights.py``) and stay in their stored dtype; each matrix is
upcast only inside its own product, one layer and one expert at a time.
All products run at ``precision="highest"``.  ``quant="int8"`` is the
control: every weight product from int8 values, the weight quantised per
output column and the activation per row (symmetric, absmax / 127).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
LATENT_EPS = 1e-6       # modeling_deepseek.py's q_a / kv_a RMSNorm default


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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(cfg: dict) -> np.ndarray:
    """DeepseekV3YarnRotaryEmbedding's frequencies (plain RoPE without
    ``rope_scaling``)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    f_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return f_extra
    rs = dict(rs)
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    f_inter = f_extra / factor

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return f_inter * (1 - mask) + f_extra * mask


def _rope(x, positions, cfg):
    """(S, H, r): interleaved pairs de-interleaved to [evens ‖ odds], then
    rotate-half, as DeepSeek's apply_rotary_pos_emb."""
    rs = dict(cfg.get("rope_scaling") or {})
    m = (_mscale(rs["factor"], rs["mscale"])
         / _mscale(rs["factor"], rs["mscale_all_dim"])) if rs else 1.0
    ang = positions.astype(F32)[:, None] * jnp.asarray(_inv_freq(cfg), F32)
    emb = jnp.concatenate([ang, ang], -1)
    cos, sin = (jnp.cos(emb) * m)[:, None], (jnp.sin(emb) * m)[:, None]
    s, h, r = x.shape
    x = x.reshape(s, h, r // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, r)
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + rot * sin


def _scale(cfg):
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = dict(cfg.get("rope_scaling") or {})
    if rs.get("mscale_all_dim"):
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _attention(p, x, cfg: dict, quant):
    s = x.shape[0]
    h, dn, dr = cfg["n_heads"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"]
    dv, c = cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(s)
    if cfg.get("q_lora_rank"):
        q = _mm(_rms(_mm(x, p["wq_a"], quant), p["q_norm"]["scale"],
                     LATENT_EPS), p["wq_b"], quant)
    else:
        q = _mm(x, p["wq"], quant)
    q = q.reshape(s, h, dn + dr)
    kv = _mm(x, p["wkv_a"], quant)
    c_kv = _rms(kv[:, :c], p["kv_norm"]["scale"], LATENT_EPS)
    k_pe = _rope(kv[:, None, c:], pos, cfg)                  # (S, 1, dr)
    kvb = _mm(c_kv, p["wkv_b"], quant).reshape(s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], -1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe, (s, h, dr))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * _scale(cfg)
    ok = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), -1)
    out = jnp.einsum("hqk,khd->qhd", probs, kvb[..., dn:], precision=HI)
    return _mm(out.reshape(s, h * dv), p["wo"], quant)


def _swiglu(w_gate, w_up, w_down, x, quant):
    h = jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant)
    return _mm(h, w_down, quant)


def _moe(p, g, x, cfg, quant):
    k = cfg["top_k"]
    scores = jax.nn.sigmoid(_mm(x, p["router"][g], quant))  # (S, router)
    sel = scores + p["score_bias"][g].astype(F32)
    top, idx = jax.lax.top_k(sel, k)
    margin = top[:, -1] - jax.lax.top_k(sel, k + 1)[0][:, -1]
    w = jnp.take_along_axis(scores, idx, 1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg.get("routed_scaling_factor", 1.0)
    gates = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)
    off = cfg.get("expert_offset", 0)
    held = jax.lax.dynamic_slice_in_dim(gates, off, cfg["n_experts"], 1)

    def one(acc, e):
        y = _swiglu(p["w_gate"][g, e], p["w_up"][g, e], p["w_down"][g, e],
                    x, quant)
        return acc + held[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(cfg["n_experts"]))
    if "shared" in p:
        sh = p["shared"]
        out = out + _swiglu(sh["w_gate"][g], sh["w_up"][g], sh["w_down"][g],
                            x, quant)
    return out, margin


@partial(jax.jit, static_argnames=("cfg_items", "moe", "quant"))
def _layer(p, x, g, *, cfg_items: tuple, moe: bool, quant):
    """Layer ``g`` of the stacked layer params ``p`` (the dense layers'
    stack when not ``moe``); returns the new residual stream and each
    token's routing margin (inf without MoE)."""
    cfg = dict(cfg_items)
    eps = cfg.get("norm_eps", 1e-6)
    attn = jax.tree.map(lambda a: a[g], p["attn"])
    x = x + _attention(attn, _rms(x, p["ln1"]["scale"][g], eps), cfg, quant)
    h = _rms(x, p["ln2"]["scale"][g], eps)
    f = p["ffn"]
    if moe:
        y, margin = _moe(f, g, h, cfg, quant)
        return x + y, margin
    return (x + _swiglu(f["w_gate"][g], f["w_up"][g], f["w_down"][g], h,
                        quant), jnp.full(x.shape[:1], jnp.inf, F32))


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(embed, final_scale, x, *, eps, quant):
    x = _rms(x, final_scale, eps)
    return _mm(x, embed["head"], quant)


def _items(cfg: dict) -> tuple:
    def frozen(v):
        if isinstance(v, dict):
            return tuple(sorted(v.items()))
        return tuple(v) if isinstance(v, list) else v
    return tuple(sorted((k, frozen(v)) for k, v in cfg.items()))


def logits(params: dict, cfg: dict, tokens, quant=None):
    """Float32 next-token logits (S, V) for one token sequence (S,), the
    first token at position 0."""
    return logits_and_margins(params, cfg, tokens, quant)[0]


def logits_and_margins(params: dict, cfg: dict, tokens, quant=None):
    """Logits (S, V) and, per token, the smallest routing margin over the
    MoE layers."""
    if cfg["layer_pattern"] != ["mla"] or \
            cfg.get("router") != "sigmoid_bias" or \
            cfg.get("activation", "swiglu") != "swiglu" or \
            cfg.get("tie_embeddings"):
        raise NotImplementedError(f"the MLA reference covers DeepSeek-V3 "
                                  f"blocks, not {cfg['name']}")
    items = _items(cfg)
    x = params["embed"]["tok"][jnp.asarray(tokens)].astype(F32)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    n_dense = cfg.get("first_dense_layers", 0)
    for g in range(n_dense):
        x, _ = _layer(params["dense_layers"][0], x, jnp.int32(g),
                      cfg_items=items, moe=False, quant=quant)
    for g in range(cfg["n_layers"] - n_dense):
        x, m = _layer(params["layers"][0], x, jnp.int32(g),
                      cfg_items=items, moe=True, quant=quant)
        margin = jnp.minimum(margin, m)
    return _head(params["embed"], params["final_norm"]["scale"], x,
                 eps=cfg.get("norm_eps", 1e-6), quant=quant), margin
