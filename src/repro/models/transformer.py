"""Decoder stack assembly: layer-group scan, cache plumbing, phase dispatch.

The model is organized as ``n_groups`` repetitions of the config's
``layer_pattern`` (e.g. ``('rglru','rglru','swa')`` for RecurrentGemma,
``('swa',)*5 + ('attn',)`` for Gemma-3).  Parameters and caches are *stacked*
over the group axis and the forward pass is a single ``lax.scan`` over
groups, which keeps HLO size flat for 126-layer models and lets remat wrap
one group at a time.

Phases
------
* ``train`` / ``prefill`` — full-sequence; prefill additionally (re)fills the
  cache.  Positions are uniform (scalar offset 0).
* ``decode`` — Sq in [1, 16] new tokens per sequence at per-sequence
  positions ``cache['pos']`` (B,).  Writes are performed eagerly; the
  returned ``pending`` pytree carries what `commit` needs to *undo* writes
  for rejected speculative tokens (ring-buffer rows, recurrent state stacks).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN, MLA, RGLRU, RWKV, SWA, ModelConfig
from repro.models import attention as attn_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv as rwkv_lib
from repro.models.attention import (apply_attention, init_attention,
                                    init_kv_cache, init_paged_kv_pool,
                                    paged_row_indices, quantize_rows,
                                    restore_rejected_rows)
from repro.models.layers import (apply_mlp, apply_norm, embed_tokens,
                                 embedding_specs, init_embedding, init_mlp,
                                 init_norm, mlp_specs, norm_specs, unembed)

MAX_DECODE_TOKENS = 16


def _sqrt_factor(n: int, threshold: int = 8) -> int:
    """Outer superblock count for sqrt-remat (1 = disabled)."""
    if n < threshold:
        return 1
    best = 1
    import math
    root = math.isqrt(n)
    for k in range(root, 0, -1):
        if n % k == 0:
            best = k
            break
    return best if best > 1 else 1


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _norm_fn(cfg: ModelConfig):
    return lambda p, x: apply_norm(p, x, cfg.norm, cfg.norm_eps)


def _init_ffn(key, cfg: ModelConfig, use_moe: bool, dt) -> dict:
    if use_moe:
        return moe_lib.init_moe(key, cfg.d_model, cfg.expert_d_ff,
                                cfg.n_experts, cfg.activation, dt,
                                router_experts=cfg.router_experts,
                                n_shared=cfg.n_shared_experts,
                                score_bias=cfg.router == "sigmoid_bias")
    return init_mlp(key, cfg.d_model, cfg.d_ff, cfg.activation, dt)


def _moe_routing(cfg: ModelConfig) -> dict:
    """apply_moe's DeepSeek-V3-style routing arguments ({} for Mixtral's)."""
    if (cfg.router == "softmax" and not cfg.n_shared_experts
            and cfg.router_experts == cfg.n_experts):
        return {}
    return {"expert_offset": cfg.expert_offset,
            "routed_scaling_factor": cfg.routed_scaling_factor}


def _moe_dropless(cfg: ModelConfig, phase: str) -> bool:
    # decode steps are few-token: dropless dispatch is free there and
    # keeps speculative verification exact (no batch-dependent drops)
    return cfg.moe_dropless or phase == "decode"


def _routed_moe(cfg: ModelConfig, x: jax.Array, phase: str, mesh) -> bool:
    """Whether a serving call's MoE layers take the routed expert FFN
    (``moe.routed_ffn``, in ``local`` mode) on x (B, S, D).  The
    cache-less (training) forward never does: its kernel has no VJP."""
    b, s, _ = x.shape
    return (cfg.is_moe
            and moe_lib.select_moe_mode(cfg.n_experts, s, mesh) == "local"
            and moe_lib.routed_ffn(b * s, cfg.n_experts, cfg.top_k,
                                   cfg.router_experts,
                                   _moe_dropless(cfg, phase)))


def _apply_ffn(params: dict, cfg: ModelConfig, h: jax.Array, phase: str,
               mesh, use_moe: bool) -> jax.Array:
    if use_moe:
        cf = (float("inf") if _moe_dropless(cfg, phase)
              else cfg.capacity_factor)
        return moe_lib.apply_moe(params, h, n_experts=cfg.n_experts,
                                 top_k=cfg.top_k, activation=cfg.activation,
                                 mesh=mesh, capacity_factor=cf,
                                 **_moe_routing(cfg))
    return apply_mlp(params, h, cfg.activation)


# ---------------------------------------------------------------------------
# per-layer init / specs / apply


def init_layer(key, cfg: ModelConfig, kind: str,
               use_moe: bool = False) -> dict:
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    p = {"ln1": init_norm(cfg.d_model, cfg.norm, dt),
         "ln2": init_norm(cfg.d_model, cfg.norm, dt)}
    if kind in (ATTN, SWA):
        p["attn"] = init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim, dt)
        if cfg.encoder_decoder:
            kx = jax.random.split(ks[2], 2)
            p["xattn"] = init_attention(kx[0], cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.head_dim, dt)
            p["ln_x"] = init_norm(cfg.d_model, cfg.norm, dt)
        p["ffn"] = _init_ffn(ks[1], cfg, use_moe, dt)
    elif kind == MLA:
        p["attn"] = mla_lib.init_mla(ks[0], cfg, dt)
        p["ffn"] = _init_ffn(ks[1], cfg, use_moe, dt)
    elif kind == RGLRU:
        p["rec"] = rglru_lib.init_rglru(ks[0], cfg.d_model, cfg.rnn_width,
                                        cfg.conv_width, dt)
        p["ffn"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.activation, dt)
    elif kind == RWKV:
        p["tmix"] = rwkv_lib.init_rwkv_tmix(ks[0], cfg.d_model,
                                            cfg.rwkv_head_size, dt)
        p["cmix"] = rwkv_lib.init_rwkv_cmix(ks[1], cfg.d_model, cfg.d_ff, dt)
    else:
        raise ValueError(kind)
    return p


def _ffn_specs(cfg: ModelConfig, model_size: int, use_moe: bool) -> dict:
    if not use_moe:
        return mlp_specs(cfg.activation)
    p = moe_lib.moe_storage_specs(cfg.activation, cfg.n_experts, model_size)
    if cfg.router == "sigmoid_bias":
        p["score_bias"] = P(None)
    if cfg.n_shared_experts:
        p["shared"] = mlp_specs(cfg.activation)
    return p


def layer_specs(cfg: ModelConfig, kind: str, model_size: int,
                use_moe: bool = False) -> dict:
    p = {"ln1": norm_specs(cfg.norm), "ln2": norm_specs(cfg.norm)}
    if kind in (ATTN, SWA):
        p["attn"] = attn_lib.attention_specs()
        if cfg.encoder_decoder:
            p["xattn"] = attn_lib.attention_specs()
            p["ln_x"] = norm_specs(cfg.norm)
        p["ffn"] = _ffn_specs(cfg, model_size, use_moe)
    elif kind == MLA:
        p["attn"] = mla_lib.mla_specs(cfg)
        p["ffn"] = _ffn_specs(cfg, model_size, use_moe)
    elif kind == RGLRU:
        p["rec"] = rglru_lib.rglru_specs()
        p["ffn"] = mlp_specs(cfg.activation)
    elif kind == RWKV:
        p["tmix"] = rwkv_lib.tmix_specs()
        p["cmix"] = rwkv_lib.cmix_specs()
    return p


def apply_layer(params: dict, cfg: ModelConfig, kind: str, x: jax.Array,
                cache: dict | None, pos, phase: str, mesh=None,
                enc_out: jax.Array | None = None, use_moe: bool = False,
                block_tables: jax.Array | None = None,
                spec_tree: dict | None = None):
    """Returns (x, new_cache, pending)."""
    nf = _norm_fn(cfg)
    pending = {}
    # Megatron-style sequence parallelism: the residual stream between
    # layers is sequence-sharded on 'model' (cheap to store); gather it here
    # so weight matmuls see replicated-S activations and SPMD gathers only
    # the small FSDP weight shards — NOT the full (D, F) matrix (which it
    # would do, in f32, if S stayed 'model'-sharded through the matmul).
    from repro.models.layers import fsdp_axes, gather_seq, shard_hint
    x = gather_seq(x)
    if phase == "decode":
        # weight-stationary decode (§Perf hillclimb #2): contraction-shard
        # the tiny token block to match the weights' at-rest FSDP sharding
        # (('pod','data') on the multi-pod mesh) so the qkv projections
        # psum instead of all-gathering their weights
        ax = fsdp_axes()
        if ax is not None:
            x = shard_hint(x, None, None, ax)
    if kind in (ATTN, SWA):
        window = cfg.sliding_window if kind == SWA else None
        self_cache = None
        if cache is not None:
            self_cache = {kk: vv for kk, vv in cache.items()
                          if kk in ("k", "v", "k_scale", "v_scale")}
        out, new_kv, saved = apply_attention(
            params["attn"], nf(params["ln1"], x),
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            use_rope=cfg.use_rope, window=window,
            cache=self_cache, pos=pos, phase=phase,
            block_tables=block_tables if kind == ATTN else None,
            spec_tree=spec_tree)
        x = x + out
        if phase == "decode":
            # Weight-stationary decode (§Perf hillclimb #2): the token
            # block is tiny (B x m x D), so shard its *feature* dim to
            # match the weights' contraction sharding — the FFN matmuls
            # become local-partial + psum, moving ~MBs of activations per
            # layer instead of all-gathering the 2D-sharded weights (GBs
            # per step on a 405B model).
            from repro.models.layers import fsdp_axes, shard_hint
            ax = fsdp_axes()
            if ax is not None:
                x = shard_hint(x, None, None, ax)
        if "xattn" in params:  # encoder-decoder cross attention
            if phase in ("prefill", "train") or cache is None or \
                    "ck" not in cache:
                cross_kv = attn_lib.precompute_cross_kv(
                    params["xattn"], enc_out, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.head_dim)
            else:
                cross_kv = {"ck": cache["ck"], "cv": cache["cv"]}
            x = x + attn_lib.apply_cross_attention(
                params["xattn"], nf(params["ln_x"], x), cross_kv,
                n_heads=cfg.n_heads, head_dim=cfg.head_dim)
            if new_kv is not None:
                new_kv = dict(new_kv, **cross_kv)
        h = nf(params["ln2"], x)
        x = x + _apply_ffn(params["ffn"], cfg, h, phase, mesh, use_moe)
        if phase == "decode":
            pending = {"saved": saved}
        return x, new_kv, pending

    if kind == MLA:
        if spec_tree is not None and phase == "decode":
            raise ValueError("tree speculation does not support latent "
                             "attention (MLA)")
        out, new_kv = mla_lib.apply_mla(
            params["attn"], nf(params["ln1"], x), cfg, cache=cache, pos=pos,
            phase=phase, block_tables=block_tables)
        x = x + out
        h = nf(params["ln2"], x)
        x = x + _apply_ffn(params["ffn"], cfg, h, phase, mesh, use_moe)
        return x, new_kv, pending

    if kind == RGLRU:
        out, new_state, stack = rglru_lib.apply_rglru_block(
            params["rec"], nf(params["ln1"], x),
            cache if cache is not None
            else rglru_lib.init_rglru_state(x.shape[0], cfg.rnn_width,
                                            cfg.conv_width, x.dtype))
        x = x + out
        x = x + apply_mlp(params["ffn"], nf(params["ln2"], x), cfg.activation)
        if phase == "decode":
            pending = {"stack": stack}
        return x, new_state, pending

    if kind == RWKV:
        state = (cache if cache is not None else
                 rwkv_lib.init_rwkv_state(x.shape[0], cfg.d_model,
                                          cfg.rwkv_head_size, x.dtype))
        x, new_state, stack = rwkv_lib.apply_rwkv_block(
            params["tmix"], params["cmix"], params["ln1"], params["ln2"],
            x, state, cfg.rwkv_head_size, nf)
        if phase == "decode":
            pending = {"stack": stack}
        return x, new_state, pending

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# cache


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int,
                     max_len: int) -> dict | None:
    dt = _dtype(cfg)
    if kind == ATTN:
        c = init_kv_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim, dt,
                          quant=cfg.kv_cache_dtype == "int8")
        if cfg.encoder_decoder:
            c["ck"] = jnp.zeros((batch, cfg.encoder_len, cfg.n_kv_heads,
                                 cfg.head_dim), dt)
            c["cv"] = jnp.zeros_like(c["ck"])
        return c
    if kind == SWA:
        return init_kv_cache(batch, min(cfg.sliding_window, max_len),
                             cfg.n_kv_heads, cfg.head_dim, dt)
    if kind == MLA:
        return mla_lib.init_mla_cache(batch, max_len, cfg, dt)
    if kind == RGLRU:
        return rglru_lib.init_rglru_state(batch, cfg.rnn_width,
                                          cfg.conv_width, dt)
    if kind == RWKV:
        return rwkv_lib.init_rwkv_state(batch, cfg.d_model,
                                        cfg.rwkv_head_size, dt)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Stacked-over-groups cache: leaves get a leading (n_groups,) axis."""
    layers = []
    for kind in cfg.layer_pattern:
        one = init_layer_cache(cfg, kind, batch, max_len)
        layers.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape), one))
    return {"layers": tuple(layers),
            "pos": jnp.zeros((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# paged serving cache (block-table KV for full-attention layers)


def init_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_size: int, max_blocks_per_seq: int,
                     kv_quant: bool | None = None) -> dict:
    """Serving cache with *paged* full-attention KV.

    ATTN layers share one ``(num_blocks, block_size, ...)`` pool per layer
    group (MLA layers one latent pool, ``(num_blocks, block_size, 1,
    R)``); each sequence addresses it through its ``block_tables`` row
    (``max_blocks_per_seq`` entries, 0 = the reserved scratch block).
    Sliding-window / recurrent layers keep their per-slot state — rings
    are window-bounded, so paging them buys nothing.  ``kv_quant``
    overrides ``cfg.kv_cache_dtype`` for the pool (int8 cold blocks on an
    otherwise-fp model config).
    """
    if cfg.encoder_decoder:
        raise ValueError("paged KV serving supports decoder-only models")
    quant = (cfg.kv_cache_dtype == "int8") if kv_quant is None else kv_quant
    if quant and MLA in cfg.layer_pattern:
        raise ValueError("int8 KV pools do not support latent attention "
                         "(MLA): its pool holds one latent row a token")
    dt = _dtype(cfg)
    layers = []
    for kind in cfg.layer_pattern:
        if kind == ATTN:
            one = init_paged_kv_pool(num_blocks, block_size, cfg.n_kv_heads,
                                     cfg.head_dim, dt, quant=quant)
        elif kind == MLA:
            one = mla_lib.init_paged_mla_pool(num_blocks, block_size, cfg,
                                              dt)
        else:
            one = init_layer_cache(cfg, kind, batch,
                                   max_blocks_per_seq * block_size)
        layers.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape), one))
    return {"layers": tuple(layers),
            "pos": jnp.zeros((batch,), jnp.int32),
            "block_tables": jnp.zeros((batch, max_blocks_per_seq),
                                      jnp.int32)}


def admit_sequence_paged(cfg: ModelConfig, cache: dict, prefill: dict,
                         slot, table_row, length, n_shared) -> dict:
    """Graft a (B=1) contiguous prefill cache into batch slot ``slot`` of a
    paged serving cache.

    ATTN layers scatter prefill rows [``n_shared * block_size``, ``length``)
    into the blocks named by ``table_row`` (rows covered by prefix-shared
    blocks are skipped — their content is already in the pool); other layer
    kinds splice per-slot state exactly like the contiguous path.  Rows are
    quantized on insert when the pool is int8 and the prefill cache is not.
    ``slot``/``table_row``/``length``/``n_shared`` may be traced, so one
    compile covers every admission.
    """
    bt = cache["block_tables"]
    mbs = bt.shape[1]
    row = jnp.asarray(table_row, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    start = jnp.asarray(n_shared, jnp.int32) * _paged_block_size(cache, cfg)
    new_layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        big, small = cache["layers"][i], prefill["layers"][i]
        if kind in (ATTN, MLA):
            new_layers.append(_paged_insert_layer(big, small, row, start,
                                                  length))
        else:
            new_layers.append(jax.tree.map(
                lambda b_, s_: jax.lax.dynamic_update_index_in_dim(
                    b_, s_[:, 0].astype(b_.dtype), slot, 1), big, small))
    pos = jax.lax.dynamic_update_index_in_dim(
        cache["pos"], length.astype(cache["pos"].dtype), slot, 0)
    bt = jax.lax.dynamic_update_index_in_dim(bt, row, slot, 0)
    return {"layers": tuple(new_layers), "pos": pos, "block_tables": bt}


def _paged_block_size(cache: dict, cfg: ModelConfig) -> int:
    for i, kind in enumerate(cfg.layer_pattern):
        if kind in (ATTN, MLA):
            return next(iter(cache["layers"][i].values())).shape[2]
    raise ValueError("paged cache has no full-attention layer")


def _paged_insert_layer(pool: dict, prefill: dict, table_row, start,
                        length) -> dict:
    """Scatter one layer group's prefill rows into its block pool.

    ``pool`` leaves are (G, NB, BS, H, d); ``prefill`` leaves (G, 1, L, H,
    d).  Rows outside [start, length) are redirected to the scratch block
    (block 0), which the engine never grants.
    """
    g, nb, bs = next(iter(pool.values())).shape[:3]
    l = next(iter(prefill.values())).shape[2]
    i = jnp.arange(l, dtype=jnp.int32)
    valid = (i >= start) & (i < length)
    idx = paged_row_indices(jnp.asarray(table_row)[None, :], i[None, :],
                            bs)[0]
    idx = jnp.where(valid, idx, i % bs)          # scratch block rows
    quant_pool = "k_scale" in pool
    quant_src = "k_scale" in prefill

    def scat(p, rows):
        flat = p.reshape((nb * bs,) + p.shape[2:])
        flat = flat.at[idx].set(rows.astype(p.dtype))
        return flat.reshape(p.shape)

    out = {}
    if quant_pool and not quant_src:
        def one_group(pk, pv, psk, psv, sk, sv):
            kq, ks = quantize_rows(sk)
            vq, vs = quantize_rows(sv)
            return (scat(pk, kq), scat(pv, vq), scat(psk, ks),
                    scat(psv, vs))
        k, v, ks_, vs_ = jax.vmap(one_group)(
            pool["k"], pool["v"], pool["k_scale"], pool["v_scale"],
            prefill["k"][:, 0], prefill["v"][:, 0])
        out = {"k": k, "v": v, "k_scale": ks_, "v_scale": vs_}
    else:
        for key in pool:
            out[key] = jax.vmap(scat)(pool[key], prefill[key][:, 0])
    return out


def release_slot_paged(cache: dict, slot) -> dict:
    """Neutralize a retired slot: point its table row at the scratch block
    and rewind ``pos`` so the still-running fused step can never write
    into blocks that were freed (and possibly re-granted)."""
    bt = cache["block_tables"]
    bt = jax.lax.dynamic_update_index_in_dim(
        bt, jnp.zeros((bt.shape[1],), bt.dtype), slot, 0)
    pos = jax.lax.dynamic_update_index_in_dim(
        cache["pos"], jnp.zeros((), cache["pos"].dtype), slot, 0)
    return dict(cache, pos=pos, block_tables=bt)


def cache_specs(cfg: ModelConfig, batch_spec, seq_spec) -> dict:
    """PartitionSpecs matching :func:`init_cache` (leading group axis)."""
    layers = []
    for kind in cfg.layer_pattern:
        if kind in (ATTN, SWA):
            one = attn_lib.kv_cache_specs(
                batch_spec, seq_spec,
                quant=(kind == ATTN and cfg.kv_cache_dtype == "int8"))
            if cfg.encoder_decoder and kind == ATTN:
                one["ck"] = P(batch_spec, None, None, None)
                one["cv"] = P(batch_spec, None, None, None)
        elif kind == MLA:
            one = {"latent": P(batch_spec, seq_spec, None, None)}
        elif kind == RGLRU:
            one = rglru_lib.rglru_state_specs(batch_spec)
        else:
            one = rwkv_lib.rwkv_state_specs(batch_spec)
        layers.append(jax.tree.map(
            lambda s: P(None, *s), one,
            is_leaf=lambda s: isinstance(s, P)))
    return {"layers": tuple(layers), "pos": P(None)}


# ---------------------------------------------------------------------------
# stacked init / specs for the whole decoder


def _init_groups(keys, cfg: ModelConfig, n: int, moe_allowed: bool) -> tuple:
    """Params of ``n`` layer groups, each pattern position stacked
    (``keys``: one per pattern position)."""
    layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        gkeys = jax.random.split(keys[i], n)
        moe_i = bool(moe_allowed and cfg.is_moe and cfg.moe_pattern[i])
        layers.append(jax.vmap(
            lambda k, m=moe_i: init_layer(k, cfg, kind, m))(gkeys))
    return tuple(layers)


def init_decoder_params(key, cfg: ModelConfig) -> dict:
    """``layers`` holds the scanned groups; ``dense_layers`` (only when
    ``cfg.first_dense_layers``) the leading dense groups before them."""
    n_pat = len(cfg.layer_pattern)
    keys = jax.random.split(key, n_pat + 2)
    dt = _dtype(cfg)
    params = {
        "embed": init_embedding(keys[-2], cfg.vocab_size, cfg.d_model, dt,
                                cfg.tie_embeddings),
        "layers": _init_groups(keys[:n_pat], cfg, cfg.n_scan_groups, True),
        "final_norm": init_norm(cfg.d_model, cfg.norm, dt),
    }
    if cfg.n_dense_groups:
        params["dense_layers"] = _init_groups(
            jax.random.split(keys[-1], n_pat), cfg, cfg.n_dense_groups,
            False)
    return params


def decoder_param_specs(cfg: ModelConfig, model_size: int = 16) -> dict:
    def groups(moe_allowed):
        layers = []
        for i, kind in enumerate(cfg.layer_pattern):
            moe_i = bool(moe_allowed and cfg.is_moe and cfg.moe_pattern[i])
            one = layer_specs(cfg, kind, model_size, moe_i)
            layers.append(jax.tree.map(
                lambda s: P(None, *s), one,
                is_leaf=lambda s: isinstance(s, P)))
        return tuple(layers)

    specs = {
        "embed": embedding_specs(cfg.tie_embeddings, cfg.vocab_size,
                                 cfg.d_model, model_size),
        "layers": groups(True),
        "final_norm": norm_specs(cfg.norm),
    }
    if cfg.n_dense_groups:
        specs["dense_layers"] = groups(False)
    return specs


# ---------------------------------------------------------------------------
# forward


def forward_decoder(params: dict, cfg: ModelConfig, x: jax.Array, *,
                    phase: str, cache: dict | None = None, mesh=None,
                    enc_out: jax.Array | None = None,
                    spec_tree: dict | None = None):
    """Run the stacked decoder over embedded inputs x (B, S, D).

    Returns (hidden, new_cache, pendings).  ``enc_out`` is the encoder
    output for encoder-decoder configs (closed over by every layer).
    ``spec_tree`` (decode only) marks x as a speculation-tree buffer —
    static numpy constants closed over by every layer; see
    :func:`repro.models.attention.apply_attention`.
    """
    pos = cache["pos"] if (cache is not None and phase == "decode") else 0
    layer_caches = cache["layers"] if cache is not None else None
    # paged serving cache: block tables are read-only within a step, so
    # they ride the scan closure (not the carry) — one (B, MBS) int32 array
    # shared by every full-attention layer group
    block_tables = (cache.get("block_tables")
                    if (cache is not None and phase == "decode") else None)

    train = phase == "train"

    def apply_group(x, gparams, gcache, moe_allowed=True):
        new_caches, pendings = [], []
        for i, kind in enumerate(cfg.layer_pattern):
            moe_i = bool(moe_allowed and cfg.is_moe and cfg.moe_pattern[i])
            x, nc, pend = apply_layer(gparams[i], cfg, kind, x, gcache[i],
                                      pos, phase, mesh, enc_out=enc_out,
                                      use_moe=moe_i,
                                      block_tables=block_tables,
                                      spec_tree=spec_tree)
            new_caches.append(nc)
            pendings.append(pend)
        return x, tuple(new_caches), tuple(pendings)

    # leading dense groups (first_dense_layers): applied one by one before
    # the scan; their caches are the first entries of the stacked cache
    dense_pendings = []
    for j in range(cfg.n_dense_groups):
        gparams = jax.tree.map(lambda a, j=j: a[j], params["dense_layers"])
        if layer_caches is None:
            x, _, _ = apply_group(x, gparams, (None,) * len(cfg.layer_pattern),
                                  moe_allowed=False)
            continue
        gcache = jax.tree.map(lambda a, j=j: a[j], layer_caches)
        x, new_caches, pend = apply_group(x, gparams, gcache,
                                          moe_allowed=False)
        layer_caches = jax.tree.map(
            lambda a, n, j=j: jax.lax.dynamic_update_index_in_dim(
                a, n.astype(a.dtype), j, 0), layer_caches, new_caches)
        dense_pendings.append(pend)

    if layer_caches is not None:
        # Serving: thread the (stacked) cache through the scan *carry* and
        # update it in place per group.  Passing it as scan xs/ys instead
        # would materialize two extra full-cache copies (the sliced inputs
        # and the re-stacked outputs) — tens of GiB for 32k-context caches.
        layers, experts = params["layers"], {}
        if _routed_moe(cfg, x, phase, mesh):
            # the routed expert FFN reads the stacked expert weights in
            # place at the group's index: a scan-sliced operand of its
            # kernel would be copied whole, every expert, every step
            for i, g in enumerate(layers):
                if cfg.moe_pattern[i]:
                    experts[i] = {k: g["ffn"][k] for k in
                                  moe_lib.EXPERT_WEIGHTS if k in g["ffn"]}
            layers = tuple(
                dict(g, ffn={k: v for k, v in g["ffn"].items()
                             if k not in experts[i]}) if i in experts else g
                for i, g in enumerate(layers))

        def body(carry, group_in):
            x, cache_layers = carry
            j, gparams = group_in
            gparams = tuple(
                dict(g, ffn=dict(g["ffn"], **experts[i],
                                 layer=j - cfg.n_dense_groups))
                if i in experts else g for i, g in enumerate(gparams))
            gcache = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, 0,
                                                       keepdims=False),
                cache_layers)
            x, new_caches, pendings = apply_group(x, gparams, gcache)
            cache_layers = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), j, 0),
                cache_layers, new_caches)
            return (x, cache_layers), tuple(pendings)

        idx = jnp.arange(cfg.n_dense_groups, cfg.n_groups, dtype=jnp.int32)
        (x, new_layer_caches), pendings = jax.lax.scan(
            body, (x, layer_caches), (idx, layers))
        if dense_pendings:
            pendings = jax.tree.map(
                lambda *a: jnp.concatenate([jnp.stack(a[:-1]), a[-1]]),
                *dense_pendings, pendings)
        new_cache = {"layers": new_layer_caches, "pos": cache["pos"]}
        if block_tables is not None:
            new_cache["block_tables"] = block_tables
        return x, new_cache, pendings

    # Training / cache-less forward: plain scan over stacked params with
    # (sqrt-)remat; for large models the per-group residual carry is
    # offloaded to host memory (the paper's offload tier applied to
    # training — ZeRO-R-style activation offload).
    def body(x, gparams):
        if train and cfg.offload_carries:
            from jax.ad_checkpoint import checkpoint_name
            x = checkpoint_name(x, "group_carry")
        x, _, _ = apply_group(x, gparams, (None,) * len(cfg.layer_pattern))
        if train:
            # keep the inter-group carry sequence-sharded so the residuals
            # reverse-mode AD stores per group are 1/seq_axis per chip
            from repro.models.layers import seq_hint
            x = seq_hint(x, 1, 1)
        return x, None

    if cfg.remat and train:
        if cfg.offload_carries:
            policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["group_carry"],
                offload_src="device", offload_dst="pinned_host")
            body = jax.checkpoint(body, policy=policy)
        else:
            body = jax.checkpoint(body)

    n_outer = 1 if (cfg.offload_carries and cfg.remat and train) else (
        _sqrt_factor(cfg.n_scan_groups) if (cfg.remat and train) else 1)
    if n_outer > 1:
        # sqrt-remat: scan superblocks of groups with an outer checkpoint,
        # so only n_outer + n_inner carries are live instead of n_groups
        # (126-layer models would otherwise store one (B,S,D) residual per
        # layer group).
        n_inner = cfg.n_scan_groups // n_outer
        xs2 = jax.tree.map(
            lambda a: a.reshape(n_outer, n_inner, *a.shape[1:]),
            params["layers"])

        @jax.checkpoint
        def outer_body(x, sxs):
            return jax.lax.scan(body, x, sxs)

        x, _ = jax.lax.scan(outer_body, x, xs2)
    else:
        x, _ = jax.lax.scan(body, x, params["layers"])
    return x, None, ()


def logits_from_hidden(params: dict, cfg: ModelConfig,
                       x: jax.Array) -> jax.Array:
    h = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return unembed(params["embed"], h)


# ---------------------------------------------------------------------------
# commit for speculative decoding


def commit_cache(cfg: ModelConfig, cache: dict, pendings, n_commit,
                 sq: int) -> dict:
    """Finalize a verify step: keep ``n_commit`` (B,) of the ``sq`` written
    tokens, undo the rest, and advance ``pos``.

    ``pendings`` is the scan-stacked pending pytree from
    :func:`forward_decoder` (leaves have a leading (n_groups,) axis).
    """
    nc = jnp.asarray(n_commit, jnp.int32)
    pos = cache["pos"]
    new_layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        c = cache["layers"][i]
        pend = pendings[i]
        if kind in (ATTN, MLA):
            new_layers.append(c)  # over-written rows are invisible
        elif kind == SWA:
            saved = pend["saved"]
            if not saved:   # cache larger than window -> behaves like full
                new_layers.append(c)
            else:
                fix = jax.vmap(
                    lambda cc, sv: restore_rejected_rows(
                        cc, sv, pos, nc, cfg.sliding_window))
                new_layers.append(fix(c, saved))
        else:  # recurrent: stack index n = state after n committed tokens
            stack = pend["stack"]
            sel = rglru_lib.select_rglru_state if kind == RGLRU \
                else rwkv_lib.select_rwkv_state
            idx = jnp.clip(nc, 0, sq)
            new_layers.append(jax.vmap(lambda st: sel(st, idx))(stack))
    out = {"layers": tuple(new_layers), "pos": pos + nc}
    if "block_tables" in cache:
        out["block_tables"] = cache["block_tables"]
    return out
