"""Mixture-of-Experts FFN with capacity-based token dispatch.

Routing follows the Switch/Mixtral recipe: softmax router, top-k experts per
token, per-expert capacity ``C = ceil(tokens * top_k / E * capacity_factor)``
with overflow dropped, gate weights renormalized over the kept experts.

Distribution modes (selected per call):

* ``local`` — no mesh / single device: dispatch + grouped einsum locally.
* ``ep`` — expert parallel: experts sharded over the ``model`` mesh axis,
  tokens sharded over (data=batch, model=sequence); each chip dispatches its
  local tokens into an ``(E, C, D)`` buffer and a tiled ``all_to_all``
  exchanges rows so each chip computes only its resident experts.  This is
  the MoE analogue of the paper's per-expert weight-streaming unit.
  Requires ``E % model_axis == 0`` and ``S % model_axis == 0``.
* ``tp`` — tensor parallel fallback (decode steps, or E not divisible, e.g.
  Mixtral's 8 experts on a 16-wide axis): every chip holds all experts with
  the hidden dim sharded over ``model``; a ``psum`` completes the
  down-projection.

All modes share ``_dispatch``/``_combine``/``_expert_ffn`` so the math is
identical; ``ep``/``tp`` run inside ``jax.shard_map``.

DeepSeek-V3-style layers (``local`` mode only) add three things:
``router='sigmoid_bias'`` scores experts with a sigmoid, chooses the top-k
on score plus a per-expert correction bias, and takes the gates from the
unbiased scores (renormalized over the chosen, times a scaling factor);
shared experts (``params['shared']``, one MLP) that every token passes
through; and an expert *share*: the router keeps its published width
``router_experts`` while the layer holds and computes only experts
``[expert_offset, expert_offset + n_experts)``, so a token's result is the
sum over its chosen experts that are held here (plus the shared experts).

A serving layer scan whose calls have few tokens takes the routed path
(:func:`routed_ffn`): the expert FFN runs in ``kernels/moe_ffn.py`` over
only the experts some token chose, and reads no weight of the others.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kernel_ops
from repro.models.layers import _act, apply_mlp, dense_init, init_mlp


# ---------------------------------------------------------------------------
# params


def init_moe(key, d_model: int, d_ff: int, n_experts: int, activation: str,
             dtype, *, router_experts: int | None = None, n_shared: int = 0,
             score_bias: bool = False) -> dict:
    """Held experts' weights and the router over ``router_experts`` (the
    held count by default); ``n_shared`` shared experts of width ``d_ff``
    as one MLP; ``score_bias`` adds the sigmoid router's correction bias."""
    ks = jax.random.split(key, 4)
    gated = activation in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], d_model, router_experts or n_experts,
                             jnp.float32),
        "w_up": _expert_init(ks[1], n_experts, d_model, d_ff, dtype),
        "w_down": _expert_init(ks[2], n_experts, d_ff, d_model, dtype),
    }
    if gated:
        p["w_gate"] = _expert_init(ks[3], n_experts, d_model, d_ff, dtype)
    if score_bias:
        p["score_bias"] = jnp.zeros((router_experts or n_experts,),
                                    jnp.float32)
    if n_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 1), d_model,
                               n_shared * d_ff, activation, dtype)
    return p


def _expert_init(key, e, d_in, d_out, dtype):
    std = d_in ** -0.5
    return (std * jax.random.truncated_normal(key, -3, 3, (e, d_in, d_out))).astype(dtype)


def moe_storage_specs(activation: str, n_experts: int, model_size: int) -> dict:
    """At-rest sharding for MoE params (what the launcher places)."""
    ep = model_size > 0 and n_experts % model_size == 0
    if ep:
        w, wd = P("model", "data", None), P("model", None, "data")
    else:
        w, wd = P(None, "data", "model"), P(None, "model", "data")
    p = {"router": P(None, None), "w_up": w, "w_down": wd}
    if activation in ("swiglu", "geglu"):
        p["w_gate"] = w
    return p


def _view_specs(activation: str, mode: str) -> dict:
    """Partitioning as seen by the shard_map body."""
    if mode == "ep_psum":
        # matches the at-rest storage exactly: zero resharding at entry
        w, wd = P("model", "data", None), P("model", None, "data")
        router = P("data", None)
    elif mode == "ep":
        w, wd = P("model", None, None), P("model", None, None)
        router = P(None, None)
    else:
        w, wd = P(None, None, "model"), P(None, "model", None)
        router = P(None, None)
    p = {"router": router, "w_up": w, "w_down": wd}
    if activation in ("swiglu", "geglu"):
        p["w_gate"] = w
    return p


# ---------------------------------------------------------------------------
# shared routing math (token-local, used identically in every mode)


def _route(router_w, x_flat, n_experts: int, top_k: int, score_bias=None,
           scaling: float = 1.0):
    """Top-k routing. Returns (expert_idx (N,k), gate (N,k) f32).

    Softmax routing without ``score_bias``; with it, sigmoid scores, the
    top-k of score + bias, gates the chosen unbiased scores over their
    sum, times ``scaling``."""
    logits = x_flat.astype(jnp.float32) @ router_w
    if score_bias is None:
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, top_k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        return idx, gate
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + score_bias, top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    return idx, gate * scaling


def _capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    """cf >= n_experts/top_k (or cf=inf) gives dropless dispatch."""
    if cf == float("inf") or cf * top_k >= n_experts:
        return n_tokens
    cap = int(n_tokens * top_k * cf / n_experts) + 1
    return max(cap, 1)


def _dispatch(x_flat, idx, n_experts: int, capacity: int, held=None):
    """Scatter tokens into per-expert capacity buffers.

    ``held`` (N, k) bool marks the choices whose expert this layer holds
    (None: all); the others are dropped.
    Returns (buf (E, C, D), slot (N, k) int32 — slot < 0 means dropped).
    """
    n, k = idx.shape
    flat_e = idx.reshape(-1)                               # (N*k,)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    if held is not None:
        onehot = onehot * held.reshape(-1, 1).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot              # 1-based rank
    slot = (pos.sum(-1) - 1).astype(jnp.int32)             # (N*k,)
    keep = slot < capacity
    if held is not None:
        keep = keep & held.reshape(-1)
    slot = jnp.where(keep, slot, -1)
    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    safe_e = jnp.where(keep, flat_e, 0)
    safe_s = jnp.where(keep, slot, 0)
    buf = jnp.zeros((n_experts, capacity, x_flat.shape[-1]), x_flat.dtype)
    buf = buf.at[safe_e, safe_s].add(
        jnp.where(keep[:, None], x_flat[tok], 0).astype(x_flat.dtype))
    return buf, slot.reshape(n, k)


def _combine(y_buf, idx, slot, gate):
    """Gather expert outputs back to token order, weighted by gates."""
    n, k = idx.shape
    keep = slot >= 0
    safe_s = jnp.where(keep, slot, 0)
    picked = y_buf[idx.reshape(-1), safe_s.reshape(-1)].reshape(n, k, -1)
    picked = jnp.where(keep[..., None], picked, 0)
    return jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32),
                      gate).astype(y_buf.dtype)


def _expert_ffn(params, buf, activation: str):
    """(E, C, D) -> (E, C, D) grouped FFN."""
    if "w_gate" in params:
        h = _act(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]), activation)
        h = h * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    else:
        h = _act(jnp.einsum("ecd,edf->ecf", buf, params["w_up"]), activation)
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


# the expert weights the routed FFN reads (w_gate only where gated)
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def routed_ffn(n_tokens: int, n_experts: int, top_k: int,
               router_experts: int, dropless: bool = True) -> bool:
    """Whether a ``local`` call of ``n_tokens`` takes the routed expert FFN.

    Only where the layer holds all of its router's experts (an expert
    share's held experts serve other chips' tokens too, so none may be
    skipped), drops nothing, and at least one expert is expected to go
    unread under uniform routing: ``E (1 - k/E)^N >= 1``."""
    return (router_experts == n_experts and dropless
            and n_experts * (1 - top_k / n_experts) ** n_tokens >= 1)


def _routed_expert_ffn(params, x_flat, idx, gate, n_experts: int,
                       activation: str):
    """The chosen experts' FFN, gated and summed per token, reading only
    the weights of experts that some token chose.  ``params["layer"]``
    indexes the expert weights, stacked over the scan's layers and read
    in place."""
    chosen = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)   # (N,k,E)
    combine = (chosen * gate[..., None]).sum(1)                   # (N, E)
    used = chosen.sum((0, 1)) > 0
    active = jnp.flatnonzero(used, size=n_experts, fill_value=0)
    n_active = used.sum(dtype=jnp.int32).reshape(1)
    return kernel_ops.routed_expert_ffn(
        x_flat, combine, active, n_active,
        jnp.asarray(params["layer"], jnp.int32).reshape(1),
        *(params.get(k) for k in EXPERT_WEIGHTS), activation=activation)


# ---------------------------------------------------------------------------
# mode bodies


def _moe_local(params, x_flat, *, n_experts, top_k, capacity_factor,
               activation, expert_offset=0, routed_scaling_factor=1.0):
    n = x_flat.shape[0]
    cap = _capacity(n, top_k, n_experts, capacity_factor)
    idx, gate = _route(params["router"], x_flat, n_experts, top_k,
                       params.get("score_bias"), routed_scaling_factor)
    if "layer" in params:
        # the serving layer scan chose the routed FFN (routed_ffn) and hands
        # over the stacked expert weights with the layer's index
        out = _routed_expert_ffn(params, x_flat, idx, gate, n_experts,
                                 activation)
    else:
        held = None
        if params["router"].shape[-1] != n_experts:
            # an expert share: route over the router's width, keep the held
            local = idx - expert_offset
            held = (local >= 0) & (local < n_experts)
            idx = jnp.where(held, local, 0)
        buf, slot = _dispatch(x_flat, idx, n_experts, cap, held)
        y = _expert_ffn(params, buf, activation)
        out = _combine(y, idx, slot, gate)
    if "shared" in params:
        out = out + apply_mlp(params["shared"], x_flat, activation)
    return out


def _moe_ep_body(params, x_flat, *, n_experts, top_k, capacity_factor,
                 activation, model_axis="model"):
    """Per-chip body: tokens local shard, experts sharded on ``model``."""
    n = x_flat.shape[0]
    msize = jax.lax.axis_size(model_axis)
    cap = _capacity(n, top_k, n_experts, capacity_factor)
    idx, gate = _route(params["router"], x_flat, n_experts, top_k)
    buf, slot = _dispatch(x_flat, idx, n_experts, cap)       # (E, C, D)
    # each chip keeps experts [m*E/msize, ...); swap rows for experts
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                             tiled=True)                     # (E_loc, C*m, D)
    y = _expert_ffn(params, buf, activation)
    y = jax.lax.all_to_all(y, model_axis, split_axis=1, concat_axis=0,
                           tiled=True)                       # (E, C, D)
    return _combine(y, idx, slot, gate)


def _moe_ep_psum_body(params, x_flat, *, n_experts, top_k, capacity_factor,
                      activation, model_axis="model", data_axis="data"):
    """Fully weight-stationary decode MoE (§Perf hillclimb #3).

    Tokens are few at decode time, so the token block is replicated and
    its *feature* dim sharded over ``data`` (matching the experts' at-rest
    P('model','data',·) sharding exactly — zero resharding at entry).
    Each chip computes the partial up/gate products of its resident
    experts from its D-shard, psums the (E_loc, C, F) partials over
    ``data`` BEFORE the nonlinearity (exact), applies SwiGLU, projects
    down to its local D-shard, and a psum over ``model`` combines expert
    contributions.  Collective traffic is a few MB of activations per
    layer; the GBs of expert weights never move.
    """
    n = x_flat.shape[0]                       # x_flat: (N, D_local)
    msize = jax.lax.axis_size(model_axis)
    e_loc = n_experts // msize
    cap = _capacity(n, top_k, n_experts, capacity_factor)

    # routing: partial logits over the local D shard, psum over data
    logits = jax.lax.psum(
        x_flat.astype(jnp.float32) @ params["router"], data_axis)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    buf, slot = _dispatch(x_flat, idx, n_experts, cap)   # (E, C, D_loc)
    m_idx = jax.lax.axis_index(model_axis)
    buf_loc = jax.lax.dynamic_slice_in_dim(buf, m_idx * e_loc, e_loc, 0)

    hu = jax.lax.psum(
        jnp.einsum("ecd,edf->ecf", buf_loc, params["w_up"],
                   preferred_element_type=jnp.float32), data_axis)
    if "w_gate" in params:
        hg = jax.lax.psum(
            jnp.einsum("ecd,edf->ecf", buf_loc, params["w_gate"],
                       preferred_element_type=jnp.float32), data_axis)
        h = _act(hg, activation) * hu
    else:
        h = _act(hu, activation)
    y_loc = jnp.einsum("ecf,efd->ecd", h.astype(buf_loc.dtype),
                       params["w_down"])            # (E_loc, C, D_loc)
    y = jnp.zeros((n_experts, cap, x_flat.shape[-1]), y_loc.dtype)
    y = jax.lax.dynamic_update_slice_in_dim(y, y_loc, m_idx * e_loc, 0)
    y = jax.lax.psum(y, model_axis)
    return _combine(y, idx, slot, gate)


def _moe_tp_body(params, x_flat, *, n_experts, top_k, capacity_factor,
                 activation, model_axis="model"):
    """Per-chip body: all experts resident, hidden dim sharded on model."""
    n = x_flat.shape[0]
    cap = _capacity(n, top_k, n_experts, capacity_factor)
    idx, gate = _route(params["router"], x_flat, n_experts, top_k)
    buf, slot = _dispatch(x_flat, idx, n_experts, cap)
    y = _expert_ffn(params, buf, activation)    # partial over hidden shards
    y = jax.lax.psum(y, model_axis)
    return _combine(y, idx, slot, gate)


# ---------------------------------------------------------------------------
# public entry


def select_moe_mode(n_experts: int, seq_len: int, mesh) -> str:
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return "local"
    msize = mesh.shape["model"]
    if msize == 1:
        return "local"
    if n_experts % msize == 0:
        # all-to-all EP when the sequence can spread over 'model';
        # expert-stationary psum EP for decode steps (S < msize)
        return "ep" if seq_len % msize == 0 else "ep_psum"
    return "tp"


def apply_moe(params: dict, x: jax.Array, *, n_experts: int, top_k: int,
              activation: str, mesh=None, capacity_factor: float = 2.0,
              batch_axis="data", pod_axis=None, **routing) -> jax.Array:
    """MoE FFN over x (B, S, D).  ``routing`` (``expert_offset``,
    ``routed_scaling_factor``) configures the DeepSeek-V3-style layer,
    which runs in ``local`` mode only."""
    b, s, d = x.shape
    mode = select_moe_mode(n_experts, s, mesh)
    kw = dict(n_experts=n_experts, top_k=top_k,
              capacity_factor=capacity_factor, activation=activation)

    if mode == "local":
        return _moe_local(params, x.reshape(-1, d), **kw,
                          **routing).reshape(b, s, d)
    if (routing or "shared" in params or "score_bias" in params
            or params["router"].shape[-1] != n_experts):
        raise NotImplementedError(
            f"MoE mode {mode!r} has no shared experts, sigmoid routing or "
            f"expert share; those layers run on one device per share")

    body = {"ep": _moe_ep_body, "ep_psum": _moe_ep_psum_body,
            "tp": _moe_tp_body}[mode]
    bspec = (pod_axis, batch_axis) if pod_axis else batch_axis
    # ep: sequence sharded over model so token work is spread;
    # ep_psum (decode): token block replicated, feature dim on 'data';
    # tp: tokens replicated over model
    if mode == "ep_psum":
        x_spec = P(None, None, "data")
    else:
        x_spec = P(bspec, "model" if mode == "ep" else None, None)

    def shard_fn(p, xx):
        out = body(p, xx.reshape(-1, xx.shape[-1]), **kw)
        return out.reshape(xx.shape)

    return jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(_view_specs(activation, mode), x_spec),
        out_specs=x_spec, check_vma=False)(params, x)
