"""Multi-head latent attention (MLA), the DeepSeek-V2/V3 attention block.

From DeepSeek-V2 §2.1 (arXiv:2405.04434) and the ``modeling_deepseek.py``
shipped with the DeepSeek-V3 / Kimi-K2 configs.  Per token:

* queries: ``x @ wq`` (``q_lora_rank`` 0), or ``RMSNorm(x @ wq_a) @ wq_b``;
  each of the ``n_heads`` heads splits into a ``qk_nope_head_dim`` part and
  a ``qk_rope_head_dim`` part that takes the rotary embedding;
* keys and values come from one compressed row ``x @ wkv_a``: a
  ``kv_lora_rank`` latent ``c_kv`` (RMS-normed) and a ``qk_rope_head_dim``
  rotary key ``k_pe`` shared by every head.  ``wkv_b`` maps ``c_kv`` to
  each head's no-rope key (``W_UK``) and value (``W_UV``).

The cache holds only the latent row ``RMSNorm(c_kv) ‖ RoPE(k_pe)``
(``kv_lora_rank + qk_rope_head_dim`` values a token, one shared "KV
head"), zero-padded to whole 128-lane tiles (576 -> 640): the paged
kernel DMAs whole rows, and a TPU DMA cannot slice a minor dimension that
is not tile-aligned (a row-major 576-wide row takes 640 lanes of its tile
anyway).  The padding takes no part in any product: queries are padded
with zeros alike.  Prefill decompresses per-head K/V through ``wkv_b``
and runs the chunked attention.  Decode and verify use the absorbed form and never
decompress the cache: ``q_nope · W_UK`` is taken into the latent space, so
attention is MQA over the latent rows with the query ``[q_nope W_UK ‖
q_pe]``; the value is the row's first ``kv_lora_rank`` columns, and
``W_UV`` is applied to the attended latent afterwards.

Rotary: interleaved pairs as in DeepSeek's code (the rope dims are read as
(even, odd) pairs and written back as [evens ‖ odds], the same
permutation for q and k).  With ``rope_scaling`` the YaRN frequencies are
always on, as in that file, and the softmax scale is ``(nope + rope) **
-0.5 * mscale ** 2``.  The latent RMSNorms (``q_norm``, ``kv_norm``) use
that file's default epsilon, 1e-6, whatever the config's ``norm_eps``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.kernels.ref import gather_paged_kv_ref
from repro.models.attention import (_pool_scatter, attention_chunked,
                                    attention_direct, attention_mask,
                                    paged_row_indices)
from repro.models.layers import apply_norm, dense_init, init_norm

LATENT_NORM_EPS = 1e-6


# ---------------------------------------------------------------------------
# rotary (plain or YaRN), softmax scale


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Rotary frequencies of the ``qk_rope_head_dim`` dims (host numpy)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.rope_scaling_dict
    if not rs:
        return extra.astype(np.float32)
    factor = rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr_dim(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                  # 1: the original frequency
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def rope_cos_scale(cfg: ModelConfig) -> float:
    """YaRN's ``_mscale`` on cos/sin (1 where mscale == mscale_all_dim)."""
    rs = cfg.rope_scaling_dict
    if not rs:
        return 1.0
    f = rs["factor"]
    return (yarn_mscale(f, rs.get("mscale", 1))
            / yarn_mscale(f, rs.get("mscale_all_dim", 0)))


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling_dict
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           cfg: ModelConfig) -> jax.Array:
    """Rotate x (..., S, H, r) at ``positions`` (..., S): pairs (2i, 2i+1)
    turn by ``positions * inv_freq[i]`` and come out as [evens ‖ odds]."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        rope_inv_freq(cfg))
    m = rope_cos_scale(cfg)
    sin = (jnp.sin(ang) * m)[..., None, :]
    cos = (jnp.cos(ang) * m)[..., None, :]
    xf = x.astype(jnp.float32)
    xe, xo = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([xe * cos - xo * sin, xo * cos + xe * sin],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# params, caches


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 5)
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    c = cfg.kv_lora_rank
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = init_norm(cfg.q_lora_rank, "rmsnorm", dtype)
        p["wq_b"] = dense_init(ks[1], cfg.q_lora_rank, h * qk, dtype)
    else:
        p["wq"] = dense_init(ks[0], d, h * qk, dtype)
    p["wkv_a"] = dense_init(ks[2], d, c + cfg.qk_rope_head_dim, dtype)
    p["kv_norm"] = init_norm(c, "rmsnorm", dtype)
    p["wkv_b"] = dense_init(ks[3], c,
                            h * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                            dtype)
    p["wo"] = dense_init(ks[4], h * cfg.v_head_dim, d, dtype)
    return p


def mla_specs(cfg: ModelConfig) -> dict:
    p = {"wkv_a": P("data", None), "kv_norm": {"scale": P(None)},
         "wkv_b": P(None, "model"), "wo": P("model", "data")}
    if cfg.q_lora_rank:
        p.update(wq_a=P("data", None), q_norm={"scale": P(None)},
                 wq_b=P(None, "model"))
    else:
        p["wq"] = P("data", "model")
    return p


def latent_width(cfg: ModelConfig) -> int:
    """Stored width of a latent row: c_kv, k_pe, zeros to a lane tile."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def init_mla_cache(batch: int, n_slots: int, cfg: ModelConfig,
                   dtype) -> dict:
    """Contiguous latent cache: one (B, S, 1, R) row a token."""
    return {"latent": jnp.zeros((batch, n_slots, 1, latent_width(cfg)),
                                dtype)}


def init_paged_mla_pool(num_blocks: int, block_size: int, cfg: ModelConfig,
                        dtype) -> dict:
    """Latent block pool: one (NB, BS, 1, R) pool a layer, shared by the
    batch through the block tables (no K and V pools per KV head)."""
    return {"latent": jnp.zeros((num_blocks, block_size, 1,
                                 latent_width(cfg)), dtype)}


def _write_rows(buf: jax.Array, rows: jax.Array, pos) -> jax.Array:
    """Write rows (B, Sq, 1, R) at per-sequence positions of (B, S, 1, R)."""
    b = buf.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    zero = jnp.zeros((), jnp.int32)
    return jax.vmap(lambda bb, rr, p: jax.lax.dynamic_update_slice(
        bb, rr, (p, zero, zero)))(buf, rows.astype(buf.dtype), pos)


# ---------------------------------------------------------------------------
# the layer


def apply_mla(params: dict, x: jax.Array, cfg: ModelConfig, *,
              cache: dict | None = None, pos=0, phase: str = "prefill",
              block_tables: jax.Array | None = None,
              kv_chunk: int = 0) -> tuple:
    """One MLA layer over x (B, S, D); returns (out, new_cache).

    ``phase`` 'prefill'/'train': x is the whole sequence from position 0;
    a given contiguous cache gets its latent rows.  'decode': x holds Sq
    new tokens at [pos, pos + Sq), written into the cache (the block pool
    when ``block_tables`` is given) and attended in the absorbed form.
    """
    b, sq, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv, c = cfg.v_head_dim, cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = apply_norm(params["q_norm"], x @ params["wq_a"], "rmsnorm",
                       LATENT_NORM_EPS) @ params["wq_b"]
    else:
        q = x @ params["wq"]
    q = q.reshape(b, sq, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv_a = x @ params["wkv_a"]
    c_kv = apply_norm(params["kv_norm"], kv_a[..., :c], "rmsnorm",
                      LATENT_NORM_EPS)
    pos_arr = jnp.asarray(pos, jnp.int32)
    if pos_arr.ndim:
        positions = pos_arr[:, None] + jnp.arange(sq, dtype=jnp.int32)
    else:
        positions = pos_arr + jnp.arange(sq, dtype=jnp.int32)
    q_pe = apply_rope_interleaved(q_pe, positions, cfg)
    k_pe = apply_rope_interleaved(kv_a[..., None, c:], positions, cfg)
    pad = latent_width(cfg) - c - dr
    rows = jnp.concatenate(
        [c_kv[:, :, None, :], k_pe,
         jnp.zeros(k_pe.shape[:-1] + (pad,), k_pe.dtype)], axis=-1)
    w_kv_b = params["wkv_b"].reshape(c, h, dn + dv)
    w_uk, w_uv = w_kv_b[..., :dn], w_kv_b[..., dn:]
    scale = softmax_scale(cfg)

    if phase in ("prefill", "train"):
        # decompressed: per-head keys [c_kv W_UK ‖ k_pe] (dk = dn + dr) and
        # values c_kv W_UV (dv), padded to dk for the shared kernel
        k_nope = jnp.einsum("bsc,chd->bshd", c_kv, w_uk)
        v = jnp.einsum("bsc,chd->bshd", c_kv, w_uv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (b, sq, h, dr))], axis=-1)
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
        if kv_chunk == 0:
            kv_chunk = 128 if phase == "train" else 512
        out = attention_chunked(qf, k, v, positions, positions, scale,
                                kv_chunk=kv_chunk)
        out = out.reshape(b, sq, h, dn + dr)[..., :dv]
        new_cache = None
        if cache is not None:
            new_cache = {"latent": jax.lax.dynamic_update_slice(
                cache["latent"], rows.astype(cache["latent"].dtype),
                (0, 0, 0, 0))}
    elif phase == "decode":
        # absorbed: q_nope W_UK in the latent space, MQA over latent rows
        q_abs = jnp.concatenate(
            [jnp.einsum("bshd,chd->bshc", q_nope, w_uk), q_pe,
             jnp.zeros(q_pe.shape[:-1] + (pad,), q_pe.dtype)], axis=-1)
        o_lat = None
        if block_tables is not None:
            pool = cache["latent"]
            idx = paged_row_indices(
                block_tables, jnp.broadcast_to(positions, (b, sq)),
                pool.shape[1]).reshape(-1)
            pool = _pool_scatter(pool, idx, rows)
            new_cache = {"latent": pool}
            if kernel_ops.use_compiled_kernels():
                o_lat = kernel_ops.paged_mla_attention(
                    q_abs.transpose(0, 2, 1, 3), pool, block_tables,
                    jnp.broadcast_to(pos_arr, (b,)) + sq, scale=scale,
                    v_dim=c).transpose(0, 2, 1, 3)
            else:
                lat, _ = gather_paged_kv_ref(pool, pool, block_tables,
                                             dtype=q.dtype)
        else:
            lat = _write_rows(cache["latent"], rows, pos_arr)
            new_cache = {"latent": lat}
            lat = lat.astype(q.dtype)
        if o_lat is None:
            mask = attention_mask(positions, jnp.arange(
                lat.shape[1], dtype=jnp.int32), None)
            o_lat = attention_direct(q_abs, lat, lat[..., :c], mask,
                                     scale).reshape(b, sq, h, c)
        out = jnp.einsum("bshc,chd->bshd", o_lat, w_uv)
    else:
        raise ValueError(phase)
    return out.reshape(b, sq, h * dv) @ params["wo"], new_cache
