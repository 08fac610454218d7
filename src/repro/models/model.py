"""Top-level model API: init / specs / train / prefill / decode / commit.

Every architecture in ``repro.configs`` flows through these six functions;
the SpecOffload engine (``repro.core``) and the launchers call nothing
deeper.  All functions are pure and jit-friendly; ``mesh`` is a static
argument (None on single-device CPU runs).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import encdec
from repro.models.layers import embed_tokens, shard_hint
from repro.models.transformer import (cache_specs, commit_cache,
                                      decoder_param_specs, forward_decoder,
                                      init_cache, init_decoder_params,
                                      logits_from_hidden)

__all__ = [
    "init_params", "param_specs", "forward_train", "loss_fn", "prefill",
    "decode", "commit", "init_cache", "cache_specs", "shard_hint",
]




# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> dict:
    k1, k2 = jax.random.split(key)
    params = init_decoder_params(k1, cfg)
    if cfg.encoder_decoder:
        params["encoder"] = encdec.init_encoder(k2, cfg)
    return params


def param_specs(cfg: ModelConfig, model_size: int = 16) -> dict:
    specs = decoder_param_specs(cfg, model_size)
    if cfg.encoder_decoder:
        specs["encoder"] = encdec.encoder_specs(cfg)
    return specs


def _embed(params, cfg, tokens):
    x = embed_tokens(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    return shard_hint(x, "data", None, None)


def _encoder_out(params, cfg, batch):
    if not cfg.encoder_decoder:
        return None
    return encdec.apply_encoder(params["encoder"], cfg,
                                batch["encoder_frames"])


# ---------------------------------------------------------------------------
# training


def forward_train(params: dict, cfg: ModelConfig, batch: dict,
                  mesh=None) -> jax.Array:
    """batch: {'tokens': (B,S) int32, ['encoder_frames': (B,T,D)]}.

    Returns next-token logits (B, S, V) in f32.
    """
    x = _embed(params, cfg, batch["tokens"])
    enc_out = _encoder_out(params, cfg, batch)
    h, _, _ = forward_decoder(params, cfg, x, phase="train", mesh=mesh,
                              enc_out=enc_out)
    return logits_from_hidden(params, cfg, h)


def loss_fn(params: dict, cfg: ModelConfig, batch: dict,
            mesh=None, logits_chunk: int = 256) -> jax.Array:
    """Causal LM cross-entropy (next-token); ignores the last position.

    The (B, S, V) logits are never materialized: the unembed + softmax-xent
    runs in rematted chunks over the sequence (a 256k-vocab model at S=4k
    would otherwise need gigabytes of f32 logits per chip).
    """
    from repro.models.layers import apply_norm, unembed
    x = _embed(params, cfg, batch["tokens"])
    enc_out = _encoder_out(params, cfg, batch)
    h, _, _ = forward_decoder(params, cfg, x, phase="train", mesh=mesh,
                              enc_out=enc_out)
    h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
    h = h[:, :-1]
    targets = batch["tokens"][:, 1:].astype(jnp.int32)

    b, s, d = h.shape
    c = min(logits_chunk, s)
    while s % c:
        c -= 1
    n = s // c
    hc = h.reshape(b, n, c, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, c).transpose(1, 0, 2)

    def chunk_nll(total, inp):
        h_i, t_i = inp
        logits = unembed(params["embed"], h_i)            # (b, c, V) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, t_i[..., None], axis=-1)
        return total + nll.sum(), None

    total, _ = jax.lax.scan(jax.checkpoint(chunk_nll),
                            jnp.zeros((), jnp.float32), (hc, tc))
    return total / (b * s)


# ---------------------------------------------------------------------------
# serving


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, cache: dict,
            mesh=None, encoder_frames: jax.Array | None = None):
    """Process the prompt (B, L); fill the cache.

    Returns (last-position logits (B, V), cache with pos=L).
    """
    b, length = tokens.shape
    x = _embed(params, cfg, tokens)
    enc_out = None
    if cfg.encoder_decoder:
        enc_out = encdec.apply_encoder(params["encoder"], cfg, encoder_frames)
    h, new_cache, _ = forward_decoder(params, cfg, x, phase="prefill",
                                      cache=cache, mesh=mesh, enc_out=enc_out)
    logits = logits_from_hidden(params, cfg, h[:, -1:])[:, 0]
    new_cache["pos"] = jnp.full((b,), length, jnp.int32)
    return logits, new_cache


def decode(params: dict, cfg: ModelConfig, cache: dict, tokens: jax.Array,
           mesh=None, spec_tree: dict | None = None):
    """Decode/verify ``m`` new tokens (B, m) at positions cache['pos'].

    Writes the cache eagerly and returns (logits (B,m,V), cache, pendings);
    call :func:`commit` with the number of accepted tokens to finalize.
    For plain autoregressive decoding use m=1 then ``commit(..., n=1)``.
    ``spec_tree`` marks ``tokens`` as speculation-tree nodes (depth-based
    positions + ancestor masking; see
    :func:`repro.core.spec_decode.tree_spec`).
    """
    x = _embed(params, cfg, tokens)
    h, new_cache, pendings = forward_decoder(params, cfg, x, phase="decode",
                                             cache=cache, mesh=mesh,
                                             spec_tree=spec_tree)
    return logits_from_hidden(params, cfg, h), new_cache, pendings


def commit(cfg: ModelConfig, cache: dict, pendings, n_commit,
           sq: int) -> dict:
    """Accept the first ``n_commit`` (B,) of the ``sq`` decoded tokens."""
    return commit_cache(cfg, cache, pendings, n_commit, sq)


def decode_step(params: dict, cfg: ModelConfig, cache: dict,
                tokens: jax.Array, mesh=None):
    """One committed autoregressive step (B, 1) -> (logits (B,V), cache)."""
    logits, cache, pendings = decode(params, cfg, cache, tokens, mesh)
    b = tokens.shape[0]
    cache = commit(cfg, cache, pendings, jnp.ones((b,), jnp.int32), 1)
    return logits[:, 0], cache
