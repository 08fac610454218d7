"""Attention: GQA with RoPE, full/sliding-window variants, KV caches.

Two execution paths share one masking scheme:

* ``attention_chunked`` — flash-style online-softmax ``lax.scan`` over KV
  chunks; used for train/prefill where Sq is large.  Never materializes the
  (Sq, Skv) score matrix; per-step footprint is (Sq, kv_chunk).
* ``attention_direct`` — plain masked softmax; used for decode/verify where
  Sq is 1..(n_cand+1).  Works with a sequence-sharded KV cache: GSPMD
  partitions the softmax reduction (partial max/sum + all-reduce).

KV caches are fixed-size buffers.  Full-attention layers use ``S_max`` slots
indexed by logical position; sliding-window (SWA) layers use a ``window``-slot
ring buffer written at ``pos % window``.  Masks are derived *analytically*
from the scalar ``pos`` — slot ``j`` of a ring holds logical position
``p_j = (L-1) - ((L-1-j) mod W)`` for cache length ``L`` — so no slot-position
bookkeeping array is needed.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kernel_ops
from repro.models.layers import (apply_rope, dense_init, rope_table,
                                 seq_axis, seq_hint, shard_hint)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }


def attention_specs() -> dict:
    return {"wq": P("data", "model"), "wk": P("data", "model"),
            "wv": P("data", "model"), "wo": P("model", "data")}


# ---------------------------------------------------------------------------
# masking helpers


def ring_slot_positions(n_slots: int, length, window: int) -> jax.Array:
    """Logical position held by each ring-buffer slot given cache length.

    ``length`` is the number of tokens written so far — a scalar or a (B,)
    per-sequence array.  Slots not yet written get a negative position
    (always masked).  Output (n_slots,) or (B, n_slots).
    """
    j = jnp.arange(n_slots, dtype=jnp.int32)
    last = jnp.asarray(length, jnp.int32) - 1
    if last.ndim:
        last = last[:, None]
    return last - jnp.mod(last - j, jnp.asarray(window, jnp.int32))


def attention_mask(q_positions: jax.Array, kv_positions: jax.Array,
                   window: int | None, causal: bool = True) -> jax.Array:
    """Additive mask in f32: 0 allowed / NEG_INF disallowed.

    ``q_positions`` is (Sq,) or (B, Sq); ``kv_positions`` is (Skv,) or
    (B, Skv).  The result broadcasts to (..., Sq, Skv).
    """
    qp = q_positions[..., :, None]
    kp = kv_positions[..., None, :]
    ok = kp >= 0
    if causal:
        ok = ok & (kp <= qp)
    if window is not None:
        ok = ok & (kp > qp - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _tree_decode_mask(base: jax.Array, tree_mask, n_kv: int) -> jax.Array:
    """Additive (B, Sq, n_kv) mask for one tree-speculation decode step.

    ``base`` (B,) is where the speculation buffer starts in the cache;
    ``tree_mask`` (Sq, W) is the static ancestor-or-self visibility of
    the Sq fed nodes over the W buffer rows written so far.  Committed
    rows (< base) stay fully visible, buffer rows [base, base+W) follow
    the tree mask, and stale rows past the buffer are hidden.
    """
    tm = jnp.asarray(np.asarray(tree_mask))
    w = tm.shape[1]
    kv_idx = jnp.arange(n_kv, dtype=jnp.int32)[None, :]
    col = kv_idx - base[:, None]                            # (B, n_kv)
    allowed = jnp.transpose(tm[:, jnp.clip(col, 0, w - 1)], (1, 0, 2))
    ok = (col < 0)[:, None, :] | (((col >= 0) & (col < w))[:, None, :]
                                  & allowed)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# attention cores (GQA-aware)


def _split_gqa(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, S, Hq, d) -> (B, S, n_kv, g, d)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def attention_direct(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array, scale: float) -> jax.Array:
    """Masked softmax attention; q (B,Sq,Hq,d), k/v (B,Skv,Hkv,d).

    ``mask`` is (Sq, Skv) or per-sequence (B, Sq, Skv).
    """
    n_kv = k.shape[2]
    qg = _split_gqa(q, n_kv)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    s = s + mask[:, None, None]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    b, sq = q.shape[:2]
    return out.reshape(b, sq, -1).astype(q.dtype)


def _chunk_kv(k, v, kv_positions, kv_chunk):
    b, skv, n_kv, d = k.shape
    kv_chunk = min(kv_chunk, skv)
    n_chunks = math.ceil(skv / kv_chunk)
    pad = n_chunks * kv_chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-1)
    kc = k.reshape(b, n_chunks, kv_chunk, n_kv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, n_kv, d).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(n_chunks, kv_chunk)
    return kc, vc, pc, pad


def _flash_forward(q, k, v, q_positions, kv_positions, scale, window,
                   causal, kv_chunk):
    """Online-softmax forward; returns (out (b,sq,hq*d), lse (b,h,g,sq))."""
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    qg = _split_gqa(q, n_kv)
    kc, vc, pc, _ = _chunk_kv(k, v, kv_positions, kv_chunk)

    def step(carry, inputs):
        m, l, acc = carry
        k_i, v_i, kvpos_i = inputs
        mask_i = attention_mask(q_positions, kvpos_i, window, causal)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_i,
                       preferred_element_type=jnp.float32) * scale
        s = s + mask_i[None, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_i.dtype), v_i,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    g = hq // n_kv
    # keep the online-softmax carry sequence-sharded (context parallelism)
    m0 = seq_hint(jnp.full((b, n_kv, g, sq), NEG_INF, jnp.float32), 3, 0)
    l0 = seq_hint(jnp.zeros((b, n_kv, g, sq), jnp.float32), 3, 0)
    a0 = seq_hint(jnp.zeros((b, n_kv, g, sq, d), jnp.float32), 3, 1)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq * d)
    return out.astype(q.dtype), lse


def attention_chunked(q, k, v, q_positions, kv_positions, scale: float,
                      window: int | None = None, causal: bool = True,
                      kv_chunk: int = 512):
    """Keyword-friendly wrapper over the custom-VJP flash attention."""
    return _attention_flash(q, k, v, q_positions, kv_positions, scale,
                            window, causal, kv_chunk)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attention_flash(q, k, v, q_positions, kv_positions,
                     scale: float, window: int | None,
                     causal: bool, kv_chunk: int):
    """Flash-style attention (pure jnp) with a recompute backward.

    Forward scans KV chunks with an online softmax, never materializing the
    (Sq, Skv) score matrix.  The backward pass is a custom VJP that
    *recomputes* each chunk's probabilities from the saved log-sum-exp
    (the standard FlashAttention backward) — without it, reverse-mode AD
    through the scan would save every per-chunk probability block, which is
    exactly the O(Sq*Skv) memory the forward avoids.
    """
    out, _ = _flash_forward(q, k, v, q_positions, kv_positions, scale,
                            window, causal, kv_chunk)
    return out


def _flash_fwd_rule(q, k, v, q_positions, kv_positions, scale, window,
                    causal, kv_chunk):
    out, lse = _flash_forward(q, k, v, q_positions, kv_positions, scale,
                              window, causal, kv_chunk)
    return out, (q, k, v, q_positions, kv_positions, out, lse)


def _flash_bwd_rule(scale, window, causal, kv_chunk, res, dout):
    q, k, v, q_positions, kv_positions, out, lse = res
    b, sq, hq, d = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    g = hq // n_kv
    seqsh = lambda z: seq_hint(z, 1, 3)
    qg = seqsh(_split_gqa(q, n_kv).astype(jnp.float32))
    do = seqsh(dout.reshape(b, sq, n_kv, g, d).astype(jnp.float32))
    og = seqsh(out.reshape(b, sq, n_kv, g, d).astype(jnp.float32))
    # D_i = rowsum(dout * out)
    D = seq_hint(jnp.einsum("bqhgd,bqhgd->bhgq", do, og), 3, 0)
    lse = seq_hint(lse, 3, 0)

    kc, vc, pc, pad = _chunk_kv(k, v, kv_positions, kv_chunk)

    def step(dq, inputs):
        k_i, v_i, kvpos_i = inputs
        mask_i = attention_mask(q_positions, kvpos_i, window, causal)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_i,
                       preferred_element_type=jnp.float32) * scale
        s = s + mask_i[None, None, None]
        p = jnp.exp(s - lse[..., None])                       # (b,h,g,q,k)
        dv_i = jnp.einsum("bhgqk,bqhgd->bkhd", p, do)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", do,
                        v_i.astype(jnp.float32))
        ds = p * (dp - D[..., None]) * scale
        dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                             k_i.astype(jnp.float32))
        dk_i = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg)
        return dq, (dk_i, dv_i)

    dq0 = seqsh(jnp.zeros((b, sq, n_kv, g, d), jnp.float32))
    dq, (dkc, dvc) = jax.lax.scan(step, dq0, (kc, vc, pc))
    dk = dkc.transpose(1, 0, 2, 3, 4).reshape(b, -1, n_kv, d)
    dv = dvc.transpose(1, 0, 2, 3, 4).reshape(b, -1, n_kv, d)
    if pad:
        dk, dv = dk[:, :skv], dv[:, :skv]
    return (dq.reshape(b, sq, hq, d).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), None, None)


_attention_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# KV cache (optionally int8-quantized: per-row-per-head absmax scales)


def init_kv_cache(batch: int, n_slots: int, n_kv_heads: int, head_dim: int,
                  dtype, quant: bool = False) -> dict:
    if quant:
        return {
            "k": jnp.zeros((batch, n_slots, n_kv_heads, head_dim), jnp.int8),
            "v": jnp.zeros((batch, n_slots, n_kv_heads, head_dim), jnp.int8),
            "k_scale": jnp.zeros((batch, n_slots, n_kv_heads, 1),
                                 jnp.float32),
            "v_scale": jnp.zeros((batch, n_slots, n_kv_heads, 1),
                                 jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, n_slots, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, n_slots, n_kv_heads, head_dim), dtype),
    }


def kv_cache_specs(batch_spec, seq_spec, quant: bool = False) -> dict:
    spec = P(batch_spec, seq_spec, None, None)
    out = {"k": spec, "v": spec}
    if quant:
        out["k_scale"] = spec
        out["v_scale"] = spec
    return out


def quantize_rows(x: jax.Array):
    """(..., d) -> (int8 values, f32 absmax/127 scale with kept dim)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-9))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# paged KV pool (block-table indexed; shared across the batch)


def init_paged_kv_pool(num_blocks: int, block_size: int, n_kv_heads: int,
                       head_dim: int, dtype, quant: bool = False) -> dict:
    """Block pool for full-attention layers: ``(NB, BS, Hkv, d)`` values
    shared by every sequence; per-sequence block tables map logical block
    -> physical block.  ``quant=True`` stores int8 values + f32 per-row
    per-head scales (cold blocks are immutable once full, so the whole
    pool can hold the quantized form — the numerics of the contiguous
    int8 cache, promoted to the paged layout)."""
    if quant:
        return {
            "k": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                           jnp.int8),
            "v": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                           jnp.int8),
            "k_scale": jnp.zeros((num_blocks, block_size, n_kv_heads, 1),
                                 jnp.float32),
            "v_scale": jnp.zeros((num_blocks, block_size, n_kv_heads, 1),
                                 jnp.float32),
        }
    return {
        "k": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                       dtype),
        "v": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                       dtype),
    }


def paged_row_indices(block_tables: jax.Array, positions: jax.Array,
                      block_size: int) -> jax.Array:
    """Flat pool-row index for each logical ``positions`` (B, N) entry.

    Out-of-table positions are clamped to the last table entry and null
    (<= 0) table entries resolve to block 0 — the engine reserves block 0
    as a scratch block that is never granted, so clamped/dead writes land
    there harmlessly.
    """
    bt = block_tables.astype(jnp.int32)
    mbs = bt.shape[1]
    blk = jnp.clip(positions // block_size, 0, mbs - 1)
    bids = jnp.maximum(jnp.take_along_axis(bt, blk, axis=1), 0)
    return bids * block_size + positions % block_size


def paged_write(cache: dict, k_new: jax.Array, v_new: jax.Array,
                block_tables: jax.Array, pos) -> dict:
    """Scatter Sq new K/V rows per sequence into the shared block pool at
    logical positions [pos, pos+Sq) via the block table.  Quantizes rows
    on write when the pool is int8 (identical per-row numerics to the
    contiguous int8 cache, so decoding stays token-identical to it)."""
    bs = cache["k"].shape[1]
    b, sq = k_new.shape[:2]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos_arr[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    idx = paged_row_indices(block_tables, positions, bs).reshape(-1)
    if "k_scale" in cache:
        kq, ks = quantize_rows(k_new)
        vq, vs = quantize_rows(v_new)
        return {"k": _pool_scatter(cache["k"], idx, kq),
                "v": _pool_scatter(cache["v"], idx, vq),
                "k_scale": _pool_scatter(cache["k_scale"], idx, ks),
                "v_scale": _pool_scatter(cache["v_scale"], idx, vs)}
    return {"k": _pool_scatter(cache["k"], idx, k_new),
            "v": _pool_scatter(cache["v"], idx, v_new),
            **{kk: cache[kk] for kk in cache if kk not in ("k", "v")}}


def _pool_scatter(pool: jax.Array, flat_idx: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """Write rows (..., H, d) at flat row indices of a (NB, BS, H, d) pool.
    Duplicate indices only arise from dead slots aimed at the scratch
    block, where any write order is acceptable."""
    nb, bs = pool.shape[:2]
    flat = pool.reshape((nb * bs,) + pool.shape[2:])
    flat = flat.at[flat_idx].set(
        rows.reshape((-1,) + pool.shape[2:]).astype(pool.dtype))
    return flat.reshape(pool.shape)


def paged_gather(cache: dict, block_tables: jax.Array, dtype):
    """Per-sequence contiguous (B, MBS*BS, H, d) K/V view of the pool
    (dequantized when int8).  Reference/CPU read path — on TPU the paged
    flash-decode kernel gathers block tiles in-kernel instead."""
    from repro.kernels.ref import gather_paged_kv_ref
    return gather_paged_kv_ref(
        cache["k"], cache["v"], block_tables,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
        dtype=dtype)


def _slots_for(pos: jax.Array, i: int, n_slots: int, ring: bool) -> jax.Array:
    slot = jnp.asarray(pos, jnp.int32) + i
    return jnp.mod(slot, n_slots) if ring else slot


def _write_cache(cache: dict, k_new: jax.Array, v_new: jax.Array,
                 pos, window: int | None) -> dict:
    """Write Sq new K/V rows starting at logical ``pos`` (ring if window).

    ``pos`` may be a scalar or a per-sequence (B,) array; the per-sequence
    case vmaps a dynamic_update_slice over the batch (lowers to a batched
    scatter, which GSPMD partitions along the batch axis).
    """
    sq = k_new.shape[1]
    n_slots = cache["k"].shape[1]
    ring = window is not None
    ck, cv = cache["k"], cache["v"]
    k_new = k_new.astype(ck.dtype)
    v_new = v_new.astype(cv.dtype)

    pos_arr = jnp.asarray(pos, jnp.int32)
    if pos_arr.ndim == 0:
        for i in range(sq):
            slot = _slots_for(pos_arr, i, n_slots, ring)
            ck = jax.lax.dynamic_update_slice(ck, k_new[:, i:i + 1],
                                              (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v_new[:, i:i + 1],
                                              (0, slot, 0, 0))
        return {"k": ck, "v": cv}

    def write_one(ck_b, cv_b, kn_b, vn_b, p):
        for i in range(sq):
            slot = _slots_for(p, i, n_slots, ring)
            ck_b = jax.lax.dynamic_update_slice(ck_b, kn_b[i:i + 1],
                                                (slot, 0, 0))
            cv_b = jax.lax.dynamic_update_slice(cv_b, vn_b[i:i + 1],
                                                (slot, 0, 0))
        return ck_b, cv_b

    ck, cv = jax.vmap(write_one)(ck, cv, k_new, v_new, pos_arr)
    return {"k": ck, "v": cv}


def _gather_rows(cache: dict, pos: jax.Array, sq: int,
                 window: int | None) -> dict:
    """Read the Sq rows that a subsequent write would clobber (ring only)."""
    n_slots = cache["k"].shape[1]

    def read_one(ck_b, cv_b, p):
        ks, vs = [], []
        for i in range(sq):
            slot = _slots_for(p, i, n_slots, True)
            ks.append(jax.lax.dynamic_slice(ck_b, (slot, 0, 0),
                                            (1,) + ck_b.shape[1:]))
            vs.append(jax.lax.dynamic_slice(cv_b, (slot, 0, 0),
                                            (1,) + cv_b.shape[1:]))
        return jnp.concatenate(ks), jnp.concatenate(vs)

    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                               (cache["k"].shape[0],))
    k, v = jax.vmap(read_one)(cache["k"], cache["v"], pos_arr)
    return {"k": k, "v": v}


def restore_rejected_rows(cache: dict, saved: dict, pos, n_commit,
                          window: int | None) -> dict:
    """Undo ring-buffer writes of rejected speculative tokens.

    ``saved`` holds the pre-write rows for the Sq touched slots; row i is
    restored for sequences where ``i >= n_commit``.
    """
    sq = saved["k"].shape[1]
    n_slots = cache["k"].shape[1]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                               (cache["k"].shape[0],))
    nc = jnp.broadcast_to(jnp.asarray(n_commit, jnp.int32),
                          (cache["k"].shape[0],))

    def fix_one(ck_b, cv_b, sk_b, sv_b, p, n):
        for i in range(sq):
            slot = _slots_for(p, i, n_slots, True)
            cur_k = jax.lax.dynamic_slice(ck_b, (slot, 0, 0),
                                          (1,) + ck_b.shape[1:])
            cur_v = jax.lax.dynamic_slice(cv_b, (slot, 0, 0),
                                          (1,) + cv_b.shape[1:])
            keep = i < n
            new_k = jnp.where(keep, cur_k, sk_b[i:i + 1])
            new_v = jnp.where(keep, cur_v, sv_b[i:i + 1])
            ck_b = jax.lax.dynamic_update_slice(ck_b, new_k, (slot, 0, 0))
            cv_b = jax.lax.dynamic_update_slice(cv_b, new_v, (slot, 0, 0))
        return ck_b, cv_b

    ck, cv = jax.vmap(fix_one)(cache["k"], cache["v"], saved["k"],
                               saved["v"], pos_arr, nc)
    return {"k": ck, "v": cv}


def _prefill_ring(cache: dict, k_new: jax.Array, v_new: jax.Array,
                  window: int) -> dict:
    """Bulk-write the last ``window`` of a prefilled sequence into the ring."""
    s = k_new.shape[1]
    n_slots = cache["k"].shape[1]
    pj = ring_slot_positions(n_slots, s, window)  # logical pos per slot
    idx = jnp.clip(pj, 0, s - 1)
    ck = jnp.take(k_new, idx, axis=1).astype(cache["k"].dtype)
    cv = jnp.take(v_new, idx, axis=1).astype(cache["v"].dtype)
    return {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# full attention layer application


def apply_attention(params: dict, x: jax.Array, *,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    rope_theta: float, use_rope: bool = True,
                    window: int | None = None,
                    cache: dict | None = None, pos=0,
                    phase: str = "prefill",
                    block_tables: jax.Array | None = None,
                    kv_chunk: int = 0,
                    spec_tree: dict | None = None) -> tuple:
    """One attention layer.

    phase="prefill"/"train": x is the full sequence; if ``cache`` is given it
    is (re)filled and returned.  phase="decode": x holds Sq (>=1) new tokens
    at logical positions [pos, pos+Sq); the cache is updated and attended.
    When ``block_tables`` is given (decode only), ``cache`` is a shared
    block *pool* and reads/writes are block-table indirect (paged KV).

    ``spec_tree`` (decode only) marks x as speculation-*tree* nodes: cache
    slots stay sequential but each node's RoPE position is ``pos - prev +
    depth`` (siblings are alternatives for the same step) and visibility
    inside the buffer follows the static ancestor mask (see
    :func:`repro.core.spec_decode.tree_spec`).  Requires full attention
    (``window`` must be None).

    Returns (out, new_cache).
    """
    b, sq, _ = x.shape
    scale = head_dim ** -0.5
    # pin the flat head dim (always divisible by the mesh) to the model
    # axis: this also pins the cotangent so dWq/dWk/dWv stay sharded
    U = P.UNCONSTRAINED
    pin = lambda z: shard_hint(z, U, U, "model")
    q = pin(x @ params["wq"]).reshape(b, sq, n_heads, head_dim)
    k = pin(x @ params["wk"]).reshape(b, sq, n_kv_heads, head_dim)
    v = pin(x @ params["wv"]).reshape(b, sq, n_kv_heads, head_dim)

    pos_arr = jnp.asarray(pos, jnp.int32)
    if pos_arr.ndim:
        q_positions = pos_arr[:, None] + jnp.arange(sq, dtype=jnp.int32)
    else:
        q_positions = pos_arr + jnp.arange(sq, dtype=jnp.int32)
    tree = spec_tree is not None and phase == "decode"
    if tree:
        if window is not None:
            raise ValueError("tree speculation needs full attention: a "
                             "sliding-window ring cannot hold a branched "
                             "buffer")
        t_prev = int(spec_tree["prev"])
        t_mask = np.asarray(spec_tree["mask"])
        t_depths = jnp.asarray(np.asarray(spec_tree["depths"]), jnp.int32)
        t_base = jnp.broadcast_to(pos_arr, (b,)) - t_prev
        # logical position = committed length + depth; the cache *slot*
        # stays the sequential [pos, pos+Sq) buffer order
        q_positions = t_base[:, None] + t_depths[None, :]
    if use_rope:
        sin, cos = rope_table(q_positions, head_dim, rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

    if kv_chunk == 0:
        # training keeps smaller score blocks: the f32 (B,H,Sq,kc) chunk and
        # its backward twins are the peak-memory buffers at 4k x 128 heads
        kv_chunk = 128 if phase == "train" else 512

    saved = {}
    if phase in ("prefill", "train"):
        # context parallelism (when a sequence axis is active): shard the q
        # sequence so per-chip flash transients are Sq/axis_size; KV stays
        # batch-sharded (every chip scans all KV chunks).  Head counts of
        # the assigned archs (10, 36, 40...) often don't divide the mesh,
        # so sequence sharding is the portable choice (DESIGN.md §6).
        q = seq_hint(q, 1, 2)
        if seq_axis() == "model":
            k = shard_hint(k, "data", None, None, None)
            v = shard_hint(v, "data", None, None, None)
        out = attention_chunked(q, k, v, q_positions, q_positions, scale,
                                window=window, kv_chunk=kv_chunk)
        out = pin(out)  # flat-head on model -> dWo stays sharded
        new_cache = None
        if cache is not None:
            if window is not None and cache["k"].shape[1] < sq:
                new_cache = _prefill_ring(cache, k, v, window)
            else:  # bulk write of the whole prefix at offset 0
                zero = (0, 0, 0, 0)
                kw, vw = k, v
                new_cache = {}
                if "k_scale" in cache:  # int8 cache: quantize + store scales
                    kw, ks = quantize_rows(k)
                    vw, vs = quantize_rows(v)
                    new_cache["k_scale"] = jax.lax.dynamic_update_slice(
                        cache["k_scale"], ks, zero)
                    new_cache["v_scale"] = jax.lax.dynamic_update_slice(
                        cache["v_scale"], vs, zero)
                new_cache["k"] = jax.lax.dynamic_update_slice(
                    cache["k"], kw.astype(cache["k"].dtype), zero)
                new_cache["v"] = jax.lax.dynamic_update_slice(
                    cache["v"], vw.astype(cache["v"].dtype), zero)
    elif phase == "decode" and block_tables is not None:
        # paged pool: scatter the new rows through the block table, then
        # attend over the table's gathered view.  Full attention only —
        # ring (SWA) layers are window-bounded and stay per-slot.
        assert cache is not None and window is None
        new_cache = paged_write(cache, k, v, block_tables, pos_arr)
        # compiled block-table kernel on TPU; elsewhere the gather +
        # masked-softmax reference (interpret-mode Pallas would dominate
        # test wall time)
        if kernel_ops.use_compiled_kernels() and (not tree or t_prev == 0):
            # full-buffer tree verify (prev == 0): the kernel masks the
            # last Sq rows with per-node int32 ancestor bitmasks
            anc = (jnp.asarray(np.asarray(spec_tree["anc_bits"]))
                   if tree else None)
            out = kernel_ops.paged_decode_attention(
                q.transpose(0, 2, 1, 3), new_cache["k"], new_cache["v"],
                block_tables, jnp.broadcast_to(pos_arr, (b,)) + sq,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"), scale=scale,
                anc_bits=anc)
            out = out.transpose(0, 2, 1, 3).reshape(b, sq, -1)
        else:
            k_read, v_read = paged_gather(new_cache, block_tables, q.dtype)
            if tree:
                mask = _tree_decode_mask(t_base, t_mask, k_read.shape[1])
            else:
                kv_positions = jnp.arange(k_read.shape[1], dtype=jnp.int32)
                mask = attention_mask(q_positions, kv_positions, None)
            out = attention_direct(q, k_read, v_read, mask, scale)
    elif phase == "decode":
        assert cache is not None
        n_slots = cache["k"].shape[1]
        ring = window is not None and n_slots <= window
        quant = "k_scale" in cache
        assert not (ring and quant), "int8 cache unsupported on ring buffers"
        if ring and sq > 1:
            # Multi-token verify on a ring buffer: writing first would
            # clobber rows still visible to the *earlier* in-flight tokens,
            # so attend over a [cache ++ new] concat view, then write.
            saved = _gather_rows(cache, pos_arr, sq, window)
            old_positions = ring_slot_positions(n_slots, pos_arr, n_slots)
            k_all = jnp.concatenate([cache["k"].astype(q.dtype), k], axis=1)
            v_all = jnp.concatenate([cache["v"].astype(q.dtype), v], axis=1)
            kv_positions = jnp.concatenate(
                [old_positions,
                 jnp.broadcast_to(q_positions, (x.shape[0], sq))], axis=1)
            mask = attention_mask(q_positions, kv_positions, window)
            out = attention_direct(q, k_all, v_all, mask, scale)
            new_cache = _write_cache(cache, k, v, pos_arr, window)
        else:
            if ring:
                saved = _gather_rows(cache, pos_arr, sq, window)
            if quant:
                kq, ks = quantize_rows(k)
                vq, vs = quantize_rows(v)
                vals = _write_cache({"k": cache["k"], "v": cache["v"]},
                                    kq, vq, pos_arr, None)
                scs = _write_cache({"k": cache["k_scale"],
                                    "v": cache["v_scale"]},
                                   ks, vs, pos_arr, None)
                new_cache = {"k": vals["k"], "v": vals["v"],
                             "k_scale": scs["k"], "v_scale": scs["v"]}
                k_read = dequantize(new_cache["k"], new_cache["k_scale"],
                                    q.dtype)
                v_read = dequantize(new_cache["v"], new_cache["v_scale"],
                                    q.dtype)
            else:
                new_cache = _write_cache(cache, k, v, pos_arr,
                                         window if ring else None)
                k_read = new_cache["k"].astype(q.dtype)
                v_read = new_cache["v"].astype(q.dtype)
            length = pos_arr + sq
            if tree:
                mask = _tree_decode_mask(t_base, t_mask, n_slots)
            else:
                if ring:
                    kv_positions = ring_slot_positions(n_slots, length,
                                                       n_slots)
                else:
                    kv_positions = jnp.arange(n_slots, dtype=jnp.int32)
                mask = attention_mask(q_positions, kv_positions, window)
            out = attention_direct(q, k_read, v_read, mask, scale)
    else:
        raise ValueError(phase)

    return out @ params["wo"], new_cache, saved


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)


def init_cross_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                         head_dim: int, dtype) -> dict:
    return init_attention(key, d_model, n_heads, n_kv_heads, head_dim, dtype)


def precompute_cross_kv(params: dict, enc_out: jax.Array, *,
                        n_kv_heads: int, head_dim: int) -> dict:
    b, s, _ = enc_out.shape
    k = (enc_out @ params["wk"]).reshape(b, s, n_kv_heads, head_dim)
    v = (enc_out @ params["wv"]).reshape(b, s, n_kv_heads, head_dim)
    return {"ck": k, "cv": v}


def apply_cross_attention(params: dict, x: jax.Array, cross_kv: dict, *,
                          n_heads: int, head_dim: int) -> jax.Array:
    b, sq, _ = x.shape
    scale = head_dim ** -0.5
    q = (x @ params["wq"]).reshape(b, sq, n_heads, head_dim)
    k, v = cross_kv["ck"].astype(q.dtype), cross_kv["cv"].astype(q.dtype)
    mask = jnp.zeros((sq, k.shape[1]), jnp.float32)  # no causal mask
    out = attention_direct(q, k, v, mask, scale)
    return out @ params["wo"]
