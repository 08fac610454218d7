"""Basic building blocks: init helpers, norms, RoPE, MLPs, embeddings.

Conventions
-----------
* All ``init_*`` functions return nested dicts of arrays; the matching
  ``*_specs`` functions return the same structure of ``PartitionSpec``.
* Weight matrices are stored ``(in_features, out_features)`` so the forward
  is ``x @ w``.
* ``compute_dtype`` is carried by the caller; params are stored in the
  config dtype and normed/accumulated in float32 where it matters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


import contextvars

# Which mesh axis (if any) the *sequence* dimension of activations shards
# over inside attention / the residual carry.  None = no sequence
# parallelism (pure FSDP profiles where the batch covers the whole mesh).
_SEQ_AXIS = contextvars.ContextVar("seq_axis", default="model")


class sequence_sharding:
    """Context manager selecting the sequence-parallel axis (or None)."""

    def __init__(self, axis):
        self.axis = axis

    def __enter__(self):
        self._tok = _SEQ_AXIS.set(self.axis)
        return self

    def __exit__(self, *exc):
        _SEQ_AXIS.reset(self._tok)
        return False


def seq_axis():
    return _SEQ_AXIS.get()


def ambient_mesh():
    """The mesh installed by an enclosing ``jax.set_mesh`` scope, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    return mesh


def shard_hint(x, *spec):
    """with_sharding_constraint that no-ops when the named axes are absent
    from the ambient mesh (so the same model code runs on 1 CPU device and
    on the production mesh)."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    for s in spec:
        for n in ((s,) if not isinstance(s, tuple) else s):
            if n is None or n is P.UNCONSTRAINED:
                continue
            if n not in names:
                return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def seq_hint(x, ndim_before: int, ndim_after: int):
    """Shard dim ``ndim_before`` (the sequence dim) on the seq axis, leaving
    every other dim unconstrained; no-op when sequence parallelism is off."""
    ax = seq_axis()
    if ax is None:
        return x
    U = P.UNCONSTRAINED
    spec = [U] * ndim_before + [ax] + [U] * ndim_after
    return shard_hint(x, *spec)


def fsdp_axes():
    """The mesh axes weights' contraction dims shard over (podified on the
    multi-pod mesh) — None when no mesh is active."""
    mesh = ambient_mesh()
    if mesh is None:
        return None
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def gather_seq(x, seq_dim: int = 1):
    """Force the sequence dim replicated (Megatron-SP style gather before a
    weight matmul whose output dim shards on the same axis); no-op unless
    the seq axis is 'model' (the conflicting case)."""
    if seq_axis() != "model":
        return x
    U = P.UNCONSTRAINED
    spec = [U] * x.ndim
    spec[seq_dim] = None
    return shard_hint(x, *spec)


# ---------------------------------------------------------------------------
# init helpers


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    """Truncated-normal fan-in init (matches common LLM practice)."""
    std = d_in ** -0.5
    return (std * jax.random.truncated_normal(key, -3, 3, (d_in, d_out))).astype(dtype)


def embed_init(key, vocab: int, d_model: int, dtype) -> jax.Array:
    # the barrier keeps XLA from folding the 0.02 into the sampler's own
    # constants under jit, so jitted and eager init agree bit for bit
    z = jax.lax.optimization_barrier(jax.random.normal(key, (vocab, d_model)))
    return (z * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms


def init_norm(d: int, kind: str, dtype) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_specs(kind: str) -> dict:
    p = {"scale": P(None)}
    if kind == "layernorm":
        p["bias"] = P(None)
    return p


def apply_norm(params: dict, x: jax.Array, kind: str = "rmsnorm",
               eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    elif kind == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + eps)
        out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:
        raise ValueError(kind)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings


def rope_table(positions: jax.Array, head_dim: int, theta: float) -> tuple:
    """sin/cos tables for integer ``positions`` (any shape).

    Returns (sin, cos) with shape ``positions.shape + (head_dim//2,)`` in f32.
    """
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Rotate ``x`` (..., S, n_heads, head_dim) by per-position tables.

    ``sin``/``cos`` have shape (..., S, head_dim//2) and broadcast over the
    heads axis.
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s = sin[..., None, :]  # add head axis
    c = cos[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)


def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "w_up": dense_init(ks[1], d_model, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d_model, dtype),
        }
    return {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
    }


def mlp_specs(activation: str) -> dict:
    if activation in ("swiglu", "geglu"):
        return {"w_gate": P("data", "model"), "w_up": P("data", "model"),
                "w_down": P("model", "data")}
    return {"w_up": P("data", "model"), "w_down": P("model", "data")}


def _act(h_gate, activation: str):
    if activation == "swiglu":
        return jax.nn.silu(h_gate)
    if activation == "geglu":
        return jax.nn.gelu(h_gate)
    if activation == "gelu":
        return jax.nn.gelu(h_gate)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(h_gate))
    raise ValueError(activation)


def apply_mlp(params: dict, x: jax.Array, activation: str) -> jax.Array:
    # Pin the hidden dim to the model axis: this also ties the *cotangent*
    # sharding in reverse-mode AD, keeping dW = x^T dy sharded instead of a
    # full (D, F) f32 buffer per layer.
    U = P.UNCONSTRAINED
    pin = lambda h: shard_hint(h, *([U] * (h.ndim - 1)), "model")
    if "w_gate" in params:
        h = pin(_act(x @ params["w_gate"], activation) * (x @ params["w_up"]))
    else:
        h = pin(_act(x @ params["w_up"], activation))
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# embeddings / unembedding


def init_embedding(key, vocab: int, d_model: int, dtype, tie: bool) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"tok": embed_init(k1, vocab, d_model, dtype)}
    if not tie:
        p["head"] = dense_init(k2, d_model, vocab, dtype)
    return p


def embedding_specs(tie: bool, vocab: int = 0, d_model: int = 0,
                    model_size: int = 16, data_size: int = 16) -> dict:
    """Vocab-on-model sharding, falling back when the vocab doesn't divide
    the axis (e.g. whisper's 51865)."""
    def spec(axes_by_dim):
        out = []
        for size, pref in axes_by_dim:
            ax = None
            for cand, cand_size in pref:
                if size == 0 or cand is None or size % cand_size == 0:
                    ax = cand
                    break
            out.append(ax)
        return P(*out)

    v_axes = ((vocab, (("model", model_size), (None, 1))),
              (d_model, (("data", data_size), (None, 1))))
    p = {"tok": spec(v_axes)}
    if not tie:
        p["head"] = spec(((d_model, (("data", data_size), (None, 1))),
                          (vocab, (("model", model_size), (None, 1)))))
    return p


def embed_tokens(params: dict, tokens: jax.Array) -> jax.Array:
    return params["tok"][tokens]


def unembed(params: dict, x: jax.Array) -> jax.Array:
    w = params.get("head")
    if w is None:
        w = params["tok"].T
    return (x @ w).astype(jnp.float32)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Classic sinusoid table (whisper encoder positions), (n, d) f32."""
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10_000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
