"""Pallas TPU flash-decode: the *verification* attention of SpecOffload.

The target model verifies m = n_cand+1 (<= 16) query tokens per sequence
against a long KV cache — a skinny-q attention that is pure KV-bandwidth.
Tiling: grid = (batch*kv_heads, Skv/block_k); each program holds the full
(g*m, d) query tile for its KV head group in VMEM (g*m is tiny) and streams
(block_k, d) KV tiles from HBM, accumulating online-softmax state in VMEM
scratch.  This is the per-step hot spot of the decode phase (§4.1.2).

:func:`paged_decode_attention` is the block-table variant for the paged KV
substrate: KV lives in a shared block pool ``(num_blocks, block_size,
kv_heads, d)``, and a scalar-prefetched block table maps each sequence's
logical blocks to physical ones.  One grid program per sequence holds the
q tiles of all its KV heads and walks only the ``ceil(length /
block_size)`` pages that hold its rows: whole pages, all heads at once,
DMA'd from the pool in HBM in its stored layout, P pages a step
(:func:`pages_per_step`, from the page's bytes and a fixed VMEM budget),
the next group in flight while one is attended.  Cold blocks may be
stored int8 with per-row-per-head scales; dequantization happens on the
VMEM tile after the DMA.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, lens_ref, anc_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale: float, block_k: int, n_kv_blocks: int,
            q_offset_from_len, window: int | None, tree: bool):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (gm, d) flattened
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    length = lens_ref[0]                              # valid cache length
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    m_tokens = q_offset_from_len
    if tree:
        # speculation-tree verify: the last m_tokens cache rows hold the
        # BFS buffer; row r's visibility over them is its int32 ancestor
        # bitmask (bit j = buffer row j is an ancestor-or-self).  No
        # gathers — a shift + AND per (q row, k position).
        anc = anc_ref[...]                            # (gm, 1) int32
        spec0 = length - m_tokens                     # buffer start
        col = k_pos - spec0
        bit = jnp.right_shift(anc, jnp.clip(col, 0, 31)) & 1
        ok = (k_pos < spec0) | ((col >= 0) & (k_pos < length) & (bit > 0))
    else:
        # q rows are (g, m) flattened; row r is token r % m, at logical
        # position length - m + (r % m)
        q_tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % m_tokens
        q_pos = length - m_tokens + q_tok
        ok = (k_pos <= q_pos) & (k_pos < length)
        if window is not None:
            ok = ok & (k_pos > q_pos - window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _fin():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, scale: float | None = None,
                     window: int | None = None, block_k: int = 256,
                     anc_bits: jax.Array | None = None,
                     interpret: bool = False) -> jax.Array:
    """Verify-attention against a cache.

    q (B, Hq, m, d) — the m new tokens (already written into the cache at
    positions [len-m, len)); k/v (B, Hkv, S, d) cache; lengths (B,) valid
    cache length per sequence (= pos + m).  Causal within the m new tokens,
    unless ``anc_bits`` (m,) int32 marks them as a speculation-tree buffer:
    token i then attends committed rows plus buffer rows j with bit j of
    ``anc_bits[i]`` set (its ancestors-or-self).  Returns (B, Hq, m, d).
    """
    b, hq, m, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    tree = anc_bits is not None
    if tree and window is not None:
        raise ValueError("tree masking requires full attention")

    skv_p = math.ceil(skv / block_k) * block_k
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    nk = skv_p // block_k

    # flatten (g, m) into one q tile per KV head
    qf = (q.reshape(b, hkv, g, m, d).reshape(b * hkv, g * m, d))
    kf = k.reshape(b * hkv, skv_p, d)
    vf = v.reshape(b * hkv, skv_p, d)
    lens = jnp.repeat(lengths.astype(jnp.int32), hkv)
    if tree:  # per-q-row bitmask, repeated across the g heads of the tile
        anc = jnp.tile(anc_bits.astype(jnp.int32), g)[:, None]  # (gm, 1)
    else:
        anc = jnp.zeros((1, 1), jnp.int32)

    kernel = functools.partial(
        _kernel, scale=scale, block_k=block_k, n_kv_blocks=nk,
        q_offset_from_len=m, window=window, tree=tree)

    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec((1, g * m, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1,), lambda bh, ki: (bh,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(anc.shape, lambda bh, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g * m, d), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g * m, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * m, 1), jnp.float32),
            pltpu.VMEM((g * m, 1), jnp.float32),
            pltpu.VMEM((g * m, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, lens, anc)
    return out.reshape(b, hkv, g, m, d).reshape(b, hq, m, d)


# ---------------------------------------------------------------------------
# paged (block-table) variant

# VMEM that the paged kernel's page buffers may take.
PAGE_BUFFER_BYTES = 4 * 1024 * 1024


def pages_per_step(block_size: int, n_kv_heads: int, head_dim: int,
                   dtype, max_blocks: int) -> int:
    """How many pages (physical blocks) one step of the paged kernel moves:
    the most whose buffers fit :data:`PAGE_BUFFER_BYTES` (K and V, each
    double-buffered, and for an int8 pool their float32 dequantized
    copies), and no more than a table row holds."""
    rows = block_size * n_kv_heads * head_dim
    per_page = 4 * rows * jnp.dtype(dtype).itemsize
    if jnp.issubdtype(dtype, jnp.integer):
        per_page += 2 * rows * 4
    return max(1, min(max_blocks, PAGE_BUFFER_BYTES // per_page))


def _head_rows(ref, h: int, hkv: int) -> jax.Array:
    """KV head ``h``'s rows of a page group, as float32 ``(n, d)``.

    ``ref`` is the group viewed as ``(n * hkv, d)``, rows in (token, head)
    order as the pool stores them, so the head's rows are every
    ``hkv``-th: a strided load.  bf16 rows pack two to a 32-bit word, so
    with ``hkv`` even the load takes the words holding head ``h``'s rows,
    and the head's half of each word is its float32 top half."""
    n = ref.shape[0] // hkv
    if ref.dtype == jnp.bfloat16 and hkv % 2 == 0:
        words = ref.bitcast(jnp.uint32)[pl.ds(h // 2, n, stride=hkv // 2), :]
        words = words << 16 if h % 2 == 0 else words & jnp.uint32(0xFFFF0000)
        return pltpu.bitcast(words, jnp.float32)
    return ref[pl.ds(h, n, stride=hkv), :].astype(jnp.float32)


def _paged_kernel(bt_ref, lens_ref, q_ref, k_hbm, v_hbm, *refs, scale: float,
                  block_size: int, pages: int, max_blocks: int,
                  m_tokens: int, quant: bool, tree: bool):
    """One sequence (grid step): all its KV heads, page group by group.

    Page groups stream from the pools in HBM into two VMEM buffers: the
    DMAs of the next group (this sequence's, or the next sequence's
    first) are in flight while this one is attended.  Only the pages
    below ``lengths`` are copied; the group loop ends at the last one.
    """
    refs = list(refs)
    ks_hbm, vs_hbm = (refs.pop(0), refs.pop(0)) if quant else (None, None)
    anc_ref = refs.pop(0) if tree else None
    o_ref, kbuf, vbuf = refs.pop(0), refs.pop(0), refs.pop(0)
    ksbuf, vsbuf, stage = ((refs.pop(0), refs.pop(0), refs.pop(0)) if quant
                           else (None, None, None))
    sems, next_buf, m_scr, l_scr, acc_scr = refs
    hkv, gm, d = q_ref.shape[1:]
    n = pages * block_size                          # tokens in a group
    rows = block_size * hkv                         # rows of a page
    seq, n_seq = pl.program_id(0), pl.num_programs(0)

    def live_pages(s):
        return jnp.minimum(pl.cdiv(lens_ref[s], block_size), max_blocks)

    def group_pages(s, g):
        """How many of sequence ``s``'s page group ``g`` are live."""
        return jnp.minimum(pages, live_pages(s) - g * pages)

    pairs = [(k_hbm, kbuf), (v_hbm, vbuf)]
    if quant:
        pairs += [(ks_hbm, ksbuf), (vs_hbm, vsbuf)]

    def group_dmas(s, g, buf, act):
        """``act`` (start or wait) on the DMAs of sequence ``s``'s page
        group ``g`` into buffer ``buf``: one per live page and array."""
        @pl.loop(0, group_pages(s, g))
        def _(i):
            blk = bt_ref[s, g * pages + i]
            for src, dst in pairs:
                act(pltpu.make_async_copy(src.at[blk], dst.at[buf, i],
                                          sems.at[buf]))

    start = functools.partial(group_dmas, act=lambda c: c.start())
    wait = functools.partial(group_dmas, act=lambda c: c.wait())

    @pl.when(seq == 0)
    def _():
        start(0, 0, 0)

    buf0 = jnp.where(seq == 0, 0, next_buf[0])
    n_groups = pl.cdiv(live_pages(seq), pages)

    @pl.when((n_groups == 0) & (seq + 1 < n_seq))
    def _():                                        # nothing to read here
        start(seq + 1, 0, buf0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    length = lens_ref[seq]                          # valid tokens (= pos+m)

    def attend(g, buf):
        k_pos = g * n + jax.lax.broadcasted_iota(jnp.int32, (gm, n), 1)
        if tree:
            # ancestor-bitmask masking of the BFS buffer (last m_tokens
            # rows); see _kernel
            anc = anc_ref[...]                      # (gm, 1) int32
            spec0 = length - m_tokens
            col = k_pos - spec0
            bit = jnp.right_shift(anc, jnp.clip(col, 0, 31)) & 1
            ok = (k_pos < spec0) | ((col >= 0) & (k_pos < length) & (bit > 0))
        else:
            q_tok = jax.lax.broadcasted_iota(jnp.int32, (gm, n), 0) % m_tokens
            ok = (k_pos <= length - m_tokens + q_tok) & (k_pos < length)
        # rows past the length (the last page's tail, and buffer slots no
        # live page filled) hold anything: their p is 0, and so is their v
        live = (g * n + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
                < length)
        if quant:
            # dequantize each live page on its VMEM tile: a page's scales
            # are one lane row, taken as a (rows, 1) column
            @pl.loop(0, group_pages(seq, g))
            def _(i):
                for c, (xbuf, sbuf) in enumerate(((kbuf, ksbuf),
                                                  (vbuf, vsbuf))):
                    stage[c, pl.ds(i * rows, rows), :] = (
                        xbuf[buf, i].astype(jnp.float32)
                        * sbuf[buf, i].T[:rows])
            kg, vg = stage.at[0], stage.at[1]
        else:
            kg = kbuf.at[buf].reshape(pages * rows, d)
            vg = vbuf.at[buf].reshape(pages * rows, d)
        for h in range(hkv):
            k = _head_rows(kg, h, hkv)                   # (n, d)
            v = jnp.where(live, _head_rows(vg, h, hkv), 0.0)
            q = q_ref[0, h].astype(jnp.float32)          # (gm, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(ok, s * scale, NEG_INF)
            m_prev, l_prev = m_scr[h], l_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_prev * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    def body(g, buf):
        nxt = 1 - buf

        @pl.when(g + 1 < n_groups)
        def _():
            start(seq, g + 1, nxt)

        @pl.when((g + 1 == n_groups) & (seq + 1 < n_seq))
        def _():
            start(seq + 1, 0, nxt)

        wait(seq, g, buf)
        attend(g, buf)
        return nxt

    next_buf[0] = jax.lax.fori_loop(0, n_groups, body, buf0)
    o_ref[0] = (acc_scr[...] /
                jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           scale: float | None = None,
                           anc_bits: jax.Array | None = None,
                           interpret: bool = False) -> jax.Array:
    """Verify-attention against a paged (block-pool) cache.

    q (B, Hq, m, d) — the m new tokens, already written into the pool at
    logical positions [len-m, len); k_pool/v_pool (NB, BS, Hkv, d) shared
    block pool (int8 when ``k_scale``/``v_scale`` (NB, BS, Hkv, 1) are
    given); block_tables (B, MBS) int32 physical block per logical block
    (entries past a sequence's ``ceil(len / BS)`` pages are never read, so
    they may be anything); lengths (B,) valid tokens per sequence
    (= pos + m).  Full causal attention (no sliding-window support — ring
    layers stay unpaged by design), unless ``anc_bits`` (m,) int32 marks
    the m tokens as a speculation-tree buffer (per-row ancestor
    bitmasks; see :func:`decode_attention`).  Returns (B, Hq, m, d).

    Tiling: one grid step per sequence, holding the q tile of all its KV
    heads, (Hkv, g*m, d).  The pools stay in HBM as stored; each DMA moves
    one whole page, its BS*Hkv rows of d in (token, head) order, all
    heads at once.  A step walks only the sequence's ``ceil(len / BS)``
    live pages, in groups of P (:func:`pages_per_step`: the most pages
    whose buffers fit :data:`PAGE_BUFFER_BYTES`), the next group's DMAs
    in flight while one group is attended; each KV head reads its rows of
    the group with a strided load.  The last group's rows past the length
    (its last page's tail, buffer slots no live page filled) are masked
    out.  The math is the online softmax of :func:`decode_attention`, in
    float32.  int8 pools: each live page is dequantized with its row
    scales on its VMEM tile; the scale pools are read as one row a block,
    padded to whole 128-lane tiles.
    """
    b, hq, m, d = q.shape
    nb, bs, hkv, _ = k_pool.shape
    mbs = block_tables.shape[1]
    g = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    quant = k_scale is not None
    tree = anc_bits is not None
    pages = pages_per_step(bs, hkv, d, k_pool.dtype, mbs)

    # one q tile per sequence: (kv head, (g, m)-flattened rows, d), as in
    # the contiguous kernel
    qf = q.reshape(b, hkv, g * m, d)
    # a page is its (BS * Hkv, d) rows, (token, head) order: the stored
    # layout itself wherever Hkv fills whole sublane tiles (a free
    # reshape); for other Hkv the reshape is a relayout copy, as the
    # head-major transpose it replaces was
    rows = bs * hkv
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    args = [qf, k_pool.reshape(nb, rows, d), v_pool.reshape(nb, rows, d)]
    in_specs = [pl.BlockSpec((1, hkv, g * m, d), lambda i, *_: (i, 0, 0, 0)),
                hbm, hbm]
    scratch = [pltpu.VMEM((2, pages, rows, d), k_pool.dtype)] * 2
    if quant:
        # a page's scales as one row, padded to whole 128-lane tiles
        lanes = pl.cdiv(rows, 128) * 128
        args += [jnp.pad(x.reshape(nb, 1, rows),
                         ((0, 0), (0, 0), (0, lanes - rows)))
                 for x in (k_scale, v_scale)]
        in_specs += [hbm, hbm]
        scratch += [pltpu.VMEM((2, pages, 1, lanes), jnp.float32)] * 2
        scratch.append(pltpu.VMEM((2, pages * rows, d), jnp.float32))
    if tree:  # per-q-row bitmask, repeated across the g heads of the tile
        args.append(jnp.tile(anc_bits.astype(jnp.int32), g)[:, None])
        in_specs.append(pl.BlockSpec((g * m, 1), lambda i, *_: (0, 0)))
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),   # buffer of the next group
                pltpu.VMEM((hkv, g * m, 1), jnp.float32),
                pltpu.VMEM((hkv, g * m, 1), jnp.float32),
                pltpu.VMEM((hkv, g * m, d), jnp.float32)]

    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=bs, pages=pages,
        max_blocks=mbs, m_tokens=m, quant=quant, tree=tree)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, g * m, d),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * m, d), q.dtype),
        # sequential steps: a step starts the next one's first DMAs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)
    return out.reshape(b, hq, m, d)
