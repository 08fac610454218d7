"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are small, obviously-correct implementations — the kernels' tests
sweep shapes/dtypes and assert_allclose against them.  They intentionally
materialize full score matrices etc. (oracle clarity over efficiency).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, scale=None, causal=True, window=None):
    """q (B,Hq,Sq,d), k/v (B,Hkv,Skv,d) -> (B,Hq,Sq,d)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32)) * scale
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths, *, scale=None, window=None,
                         anc_mask=None):
    """q (B,Hq,m,d); k/v (B,Hkv,S,d); lengths (B,). Causal over the m new
    tokens at positions [len-m, len) — or, when ``anc_mask`` (m, m) bool
    is given, ancestor-or-self tree masking of the m-row speculation
    buffer (committed rows < len-m stay fully visible)."""
    b, hq, m, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, g, m, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32)) * scale
    if anc_mask is not None:
        assert window is None, "tree masking requires full attention"
        am = jnp.asarray(anc_mask)
        kp2 = jnp.arange(skv)[None, :]
        col = kp2 - (lengths[:, None] - m)            # (B, S)
        allowed = jnp.transpose(am[:, jnp.clip(col, 0, m - 1)], (1, 0, 2))
        ok = ((col < 0)[:, None, :]
              | (((col >= 0) & (col < m))[:, None, :] & allowed))
    else:
        kp = jnp.arange(skv)[None, None, :]
        qp = (lengths[:, None, None] - m
              + jnp.arange(m)[None, :, None])        # (B, m, 1)
        ok = (kp <= qp) & (kp < lengths[:, None, None])
        if window is not None:
            ok &= kp > qp - window
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, m, d).astype(q.dtype)


def gather_paged_kv_ref(k_pool, v_pool, block_tables, *, k_scale=None,
                        v_scale=None, dtype=jnp.float32):
    """Materialize per-sequence contiguous KV from a block pool.

    k_pool/v_pool (NB, BS, H, d) [int8 when scales (NB, BS, H, 1) given];
    block_tables (B, MBS) -> k/v (B, MBS*BS, H, d) in ``dtype``.  This is
    the CPU-CI fallback for the paged Pallas kernel *and* the model's
    reference decode path: positions past each sequence's length hold
    garbage and must be masked by the caller.
    """
    nb, bs, h, d = k_pool.shape
    bt = jnp.maximum(block_tables.astype(jnp.int32), 0)
    b, mbs = bt.shape
    idx = (bt[:, :, None] * bs
           + jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(b, -1)
    k = k_pool.reshape(nb * bs, h, d)[idx]
    v = v_pool.reshape(nb * bs, h, d)[idx]
    if k_scale is not None:
        ks = k_scale.reshape(nb * bs, h, 1)[idx]
        vs = v_scale.reshape(nb * bs, h, 1)[idx]
        k = k.astype(jnp.float32) * ks
        v = v.astype(jnp.float32) * vs
    return k.astype(dtype), v.astype(dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths, *,
                               k_scale=None, v_scale=None, scale=None,
                               anc_mask=None):
    """Oracle for the paged kernel: gather, then contiguous decode ref."""
    k, v = gather_paged_kv_ref(k_pool, v_pool, block_tables,
                               k_scale=k_scale, v_scale=v_scale,
                               dtype=jnp.float32)
    return decode_attention_ref(q, jnp.swapaxes(k, 1, 2),
                                jnp.swapaxes(v, 1, 2), lengths,
                                scale=scale,
                                anc_mask=anc_mask).astype(q.dtype)


def paged_mla_attention_ref(q, pool, block_tables, lengths, *, v_dim,
                            scale=None):
    """Oracle for the latent-mode kernel: gather each sequence's latent
    rows (B, S, R), then causal MQA with the rows' first ``v_dim`` columns
    as values.  q (B, H, m, R) -> (B, H, m, v_dim)."""
    b, h, m, d = q.shape
    nb, bs = pool.shape[:2]
    bt = jnp.maximum(block_tables.astype(jnp.int32), 0)
    idx = (bt[:, :, None] * bs
           + jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(b, -1)
    rows = pool.reshape(nb * bs, d)[idx].astype(jnp.float32)   # (B, S, R)
    scale = d ** -0.5 if scale is None else scale
    s = jnp.einsum("bhqd,bkd->bhqk", q.astype(jnp.float32), rows) * scale
    kp = jnp.arange(rows.shape[1])[None, None, :]
    qp = lengths[:, None, None] - m + jnp.arange(m)[None, :, None]
    ok = (kp <= qp) & (kp < lengths[:, None, None])
    p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkd->bhqd", p, rows[..., :v_dim])
    return out.astype(q.dtype)


def routed_expert_ffn_ref(x, idx, gate, w_gate, w_up, w_down, *,
                          activation="swiglu"):
    """The plain per-token sum over chosen experts, in float32: token n
    adds ``gate[n, j] * FFN_{idx[n, j]}(x[n])`` for each of its k
    choices.  x (N, D); idx, gate (N, k); w_gate (None: ungated) /
    w_up (E, D, F), w_down (E, F, D) -> (N, D) f32."""
    act = jax.nn.silu if activation == "swiglu" else jax.nn.gelu
    xf = x.astype(jnp.float32)
    out = jnp.zeros(xf.shape, jnp.float32)
    for j in range(idx.shape[1]):
        e = idx[:, j]
        up = jnp.einsum("nd,ndf->nf", xf, w_up[e].astype(jnp.float32))
        if w_gate is None:
            h = act(up)
        else:
            h = act(jnp.einsum("nd,ndf->nf", xf,
                               w_gate[e].astype(jnp.float32))) * up
        y = jnp.einsum("nf,nfd->nd", h, w_down[e].astype(jnp.float32))
        out = out + gate[:, j:j + 1].astype(jnp.float32) * y
    return out


def rglru_scan_ref(a, gated, h0):
    def step(h, inp):
        a_t, g_t = inp
        h = a_t * h + g_t
        return h, h

    _, hs = jax.lax.scan(step, h0, (jnp.swapaxes(a, 0, 1),
                                    jnp.swapaxes(gated, 0, 1)))
    return jnp.swapaxes(hs, 0, 1)


def wkv6_ref(r, k, v, w, u, s0):
    """r/k/v/w (B,H,S,hd) f32; u (H,hd); s0 (B,H,hd,hd)."""
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                     # (B,H,hd)
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhi,bhij->bhj", r_t,
                       S + u[None, :, :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, y

    sw = lambda z: jnp.swapaxes(z, 0, 2).swapaxes(1, 2)  # (B,H,S,..)->(S,B,H,..)
    S, yT = jax.lax.scan(step, s0, (sw(r), sw(k), sw(v), sw(w)))
    y = jnp.swapaxes(jnp.swapaxes(yT, 0, 1), 1, 2)       # -> (B,H,S,hd)
    return y, S
