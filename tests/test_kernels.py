"""Per-kernel allclose tests: sweep shapes/dtypes, compare the Pallas
kernel (interpret mode on CPU) against the pure-jnp ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import pages_per_step

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype, key=KEY):
    return jax.random.normal(key, shape).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window", [
    (1, 4, 2, 128, 128, 64, True, None),
    (2, 2, 1, 256, 256, 128, True, None),
    (1, 4, 4, 128, 128, 64, True, 40),     # sliding window
    (1, 2, 2, 100, 100, 64, True, None),   # non-multiple seq (padding)
    (2, 8, 2, 128, 128, 64, False, None),  # bidirectional (encoder)
])
def test_flash_attention(dtype, b, hq, hkv, sq, skv, d, causal, window):
    ks = jax.random.split(KEY, 3)
    q = _rand((b, hq, sq, d), dtype, ks[0])
    k = _rand((b, hkv, skv, d), dtype, ks[1])
    v = _rand((b, hkv, skv, d), dtype, ks[2])
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,m,skv,d,window", [
    (2, 4, 2, 1, 256, 64, None),     # plain decode
    (2, 4, 2, 5, 256, 64, None),     # speculative verify (n_cand=4)
    (1, 8, 1, 4, 512, 128, None),    # MQA
    (2, 2, 2, 3, 300, 64, None),     # non-multiple cache length
    (1, 4, 2, 4, 256, 64, 64),       # sliding window cache
])
def test_decode_attention(dtype, b, hq, hkv, m, skv, d, window):
    ks = jax.random.split(KEY, 4)
    q = _rand((b, hq, m, d), dtype, ks[0])
    k = _rand((b, hkv, skv, d), dtype, ks[1])
    v = _rand((b, hkv, skv, d), dtype, ks[2])
    lengths = jax.random.randint(ks[3], (b,), m + 8,
                                 skv + 1).astype(jnp.int32)
    got = ops.decode_attention(q, k, v, lengths, window=window,
                               block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _paged_case(key, b, hkv, mbs, bs, d, dtype, quant=False):
    """Random pool + disjoint per-sequence block tables + lengths."""
    ks = jax.random.split(key, 4)
    nb = b * mbs + 3                     # a few never-referenced blocks
    perm = jax.random.permutation(ks[0], nb)[:b * mbs].reshape(b, mbs)
    if quant:
        kp = jax.random.randint(ks[1], (nb, bs, hkv, d), -127,
                                128).astype(jnp.int8)
        vp = jax.random.randint(ks[2], (nb, bs, hkv, d), -127,
                                128).astype(jnp.int8)
        scs = jax.random.uniform(ks[3], (2, nb, bs, hkv, 1),
                                 minval=0.01, maxval=0.1)
        scales = dict(k_scale=scs[0], v_scale=scs[1])
    else:
        kp = _rand((nb, bs, hkv, d), dtype, ks[1])
        vp = _rand((nb, bs, hkv, d), dtype, ks[2])
        scales = dict(k_scale=None, v_scale=None)
    return kp, vp, perm.astype(jnp.int32), scales


def _random_lens(*case):
    """A case whose lengths are drawn at random; its id is its shape."""
    return pytest.param(*case, None, id="-".join(map(str, case)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,m,mbs,bs,d,quant,lens", [
    _random_lens(2, 4, 2, 1, 4, 16, 64, False),  # plain paged decode
    _random_lens(2, 4, 2, 5, 4, 16, 64, False),  # speculative verify (m=5)
    _random_lens(1, 8, 1, 4, 8, 8, 128, False),  # MQA, small blocks
    _random_lens(2, 2, 2, 3, 3, 32, 64, True),   # int8 cold blocks + scales
    _random_lens(1, 4, 2, 4, 5, 16, 64, True),   # int8, MBS < full pool
    # several page groups, MBS not a multiple of the group: GQA 6 with
    # m=5 (30 q rows a KV head); lengths ending inside the first group,
    # on a group boundary, mid-page, and at the table's end
    pytest.param(4, 48, 8, 5, 10, 64, 128, False, (37, 256, 520, 640),
                 id="gqa6-m5-groups"),
    pytest.param(4, 16, 8, 4, 7, 64, 128, True, (21, 320, 350, 448),
                 id="int8-groups"),
])
def test_paged_decode_attention(dtype, b, hq, hkv, m, mbs, bs, d, quant,
                                lens):
    ks = jax.random.split(KEY, 3)
    q = _rand((b, hq, m, d), dtype, ks[0])
    kp, vp, bt, scales = _paged_case(ks[1], b, hkv, mbs, bs, d, dtype, quant)
    if lens is None:
        lengths = jax.random.randint(ks[2], (b,), m + 1,
                                     mbs * bs + 1).astype(jnp.int32)
    else:   # the case's lengths must span several page groups
        pages = pages_per_step(bs, hkv, d, kp.dtype, mbs)
        assert mbs % pages and max(lens) > pages * bs > min(lens)
        lengths = jnp.asarray(lens, jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, bt, lengths,
                                     interpret=True, **scales)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths, **scales)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("pool", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_paged_never_reads_past_length(pool):
    """Every row past a slot's length, in the pages its table names past
    the length and in the tail of its last page, is NaN: the kernel reads
    none of them, so it matches the reference run on the finite pool.  A
    table entry past the length may even be -1."""
    b, hq, hkv, m, mbs, bs, d = 3, 4, 2, 5, 6, 16, 64
    quant = pool == jnp.int8
    dtype = jnp.float32 if quant else pool
    ks = jax.random.split(KEY, 2)
    q = _rand((b, hq, m, d), dtype, ks[0])
    kp, vp, bt, scales = _paged_case(ks[1], b, hkv, mbs, bs, d, dtype, quant)
    lengths = np.array([21, 48, 5], np.int32)   # mid-page, boundary, 1 page
    # dead[block, row]: the row holds a logical position >= its slot's length
    dead = np.zeros(kp.shape[:2], bool)
    pos = np.arange(mbs * bs).reshape(mbs, bs)
    for i in range(b):
        dead[np.asarray(bt[i])] = pos >= lengths[i]
    poison = jnp.asarray(dead)[:, :, None, None]
    if quant:   # int8 rows cannot be NaN: poison their scales
        bad = {k: jnp.where(poison, jnp.nan, s) for k, s in scales.items()}
        kp_bad, vp_bad = kp, vp
    else:
        bad = scales
        kp_bad = jnp.where(poison, jnp.nan, kp).astype(kp.dtype)
        vp_bad = jnp.where(poison, jnp.nan, vp).astype(vp.dtype)
    bt_bad = bt.at[0, -(-int(lengths[0]) // bs):].set(-1)
    got = ops.paged_decode_attention(q, kp_bad, vp_bad, bt_bad,
                                     jnp.asarray(lengths), interpret=True,
                                     **bad)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, jnp.asarray(lengths),
                                          **scales)
    assert not np.isnan(np.asarray(got, np.float32)).any()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_paged_matches_contiguous_decode():
    """A paged cache holding the same rows as a contiguous cache must give
    the contiguous kernel's output exactly (table = identity shuffle)."""
    b, hq, hkv, m, bs, mbs, d = 2, 4, 2, 3, 16, 4, 64
    skv = bs * mbs
    ks = jax.random.split(KEY, 4)
    q = _rand((b, hq, m, d), jnp.float32, ks[0])
    k = _rand((b, hkv, skv, d), jnp.float32, ks[1])
    v = _rand((b, hkv, skv, d), jnp.float32, ks[2])
    lengths = jnp.array([skv - 5, skv - 17], jnp.int32)
    # pool rows [seq b, logical block j] live at physical block b*mbs + j
    kp = k.transpose(0, 2, 1, 3).reshape(b * mbs, bs, hkv, d)
    vp = v.transpose(0, 2, 1, 3).reshape(b * mbs, bs, hkv, d)
    bt = jnp.arange(b * mbs, dtype=jnp.int32).reshape(b, mbs)
    want = ops.decode_attention(q, k, v, lengths, block_k=bs,
                                interpret=True)
    got = ops.paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,d,f", [
    (4, 128, 64, 256),
    (2, 100, 128, 300),     # non-multiples (padding)
    (8, 64, 32, 128),
])
def test_moe_ffn(dtype, e, c, d, f):
    ks = jax.random.split(KEY, 4)
    buf = _rand((e, c, d), dtype, ks[0])
    wg = _rand((e, d, f), dtype, ks[1]) * 0.1
    wu = _rand((e, d, f), dtype, ks[2]) * 0.1
    wd = _rand((e, f, d), dtype, ks[3]) * 0.1
    got = ops.moe_ffn(buf, wg, wu, wd, block_c=64, block_f=128,
                      interpret=True)
    want = ref.moe_ffn_ref(buf, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("b,s,w", [(2, 64, 256), (1, 128, 100), (4, 32, 512)])
def test_rglru_scan(b, s, w):
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(_rand((b, s, w), jnp.float32, ks[0]))
    g = _rand((b, s, w), jnp.float32, ks[1])
    h0 = _rand((b, w), jnp.float32, ks[2])
    got = ops.rglru_scan(a, g, h0, block_w=128, interpret=True)
    want = ref.rglru_scan_ref(a, g, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,h,s,hd", [(1, 2, 32, 64), (2, 4, 16, 64),
                                      (1, 1, 64, 128)])
def test_wkv6(b, h, s, hd):
    ks = jax.random.split(KEY, 6)
    r = _rand((b, h, s, hd), jnp.float32, ks[0])
    k = _rand((b, h, s, hd), jnp.float32, ks[1])
    v = _rand((b, h, s, hd), jnp.float32, ks[2])
    w = jax.nn.sigmoid(_rand((b, h, s, hd), jnp.float32, ks[3]))
    u = _rand((h, hd), jnp.float32, ks[4]) * 0.1
    s0 = _rand((b, h, hd, hd), jnp.float32, ks[5]) * 0.1
    got_y, got_s = ops.wkv6(r, k, v, w, u, s0, interpret=True)
    want_y, want_s = ref.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=2e-4, atol=2e-4)


def test_flash_matches_model_attention():
    """Kernel output equals the model's chunked-attention path."""
    from repro.models.attention import attention_chunked
    b, hq, hkv, s, d = 2, 4, 2, 96, 64
    ks = jax.random.split(KEY, 3)
    q = _rand((b, s, hq, d), jnp.float32, ks[0])
    k = _rand((b, s, hkv, d), jnp.float32, ks[1])
    v = _rand((b, s, hkv, d), jnp.float32, ks[2])
    pos = jnp.arange(s)
    model_out = attention_chunked(q, k, v, pos, pos, d ** -0.5,
                                  kv_chunk=32)
    kern_out = ops.flash_attention(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3),
                                   block_q=32, block_k=32, interpret=True)
    kern_out = kern_out.transpose(0, 2, 1, 3).reshape(b, s, hq * d)
    np.testing.assert_allclose(np.asarray(kern_out), np.asarray(model_out),
                               rtol=2e-4, atol=2e-4)
