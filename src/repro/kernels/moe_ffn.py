"""Pallas TPU routed expert FFN: the chosen experts' SwiGLU, and no other's.

At decode a MoE layer sees few tokens: 16 draft tokens choosing 6 of 64
experts touch about a quarter of them.  A dense grouped FFN reads every
expert's weights; this kernel reads only the active experts'.

Inputs: the tokens ``x (N, D)``; a dense combine matrix ``G (N, E)``
(token n's gate for expert e where it chose e, else 0); the active
expert ids compacted to the front of ``active (E,)``, with their count
``n_active (1,)``, and the ``layer`` (1,) to read, all three
scalar-prefetched; the expert weights of every layer as stored,
``(L, E, D, F)``.  A layer scan hands its stacked weights over whole with
the layer's index: a custom call's operand sliced out of them would be
copied whole first, every expert's bytes and not only the active ones.
Output: ``out = sum over active e of G[:, e] * FFN_e(x)``.

Grid = (E expert slots, F / block_f).  Slot s computes expert
``active[s]`` for all N rows (compute is free at this size) and
accumulates its gated, down-projected (N, D) contribution in an f32 VMEM
scratch.  From the slot after the last active one on, every index map
keeps returning the last active block, so the pipeline issues no DMA,
and ``pl.when`` skips the compute.  ``block_f`` is the whole F where the
double-buffered weight tiles fit :data:`WEIGHT_BUFFER_BYTES` (one
contiguous DMA per weight and expert), else the largest 128-multiple that
divides F and fits; the stored weights are never padded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM for the double-buffered weight tiles of one grid step
WEIGHT_BUFFER_BYTES = 48 * 2 ** 20
# VMEM beyond the weight buffers: tokens, output, accumulator, temporaries
VMEM_HEADROOM_BYTES = 16 * 2 ** 20


def _act(h, activation: str):
    if activation == "swiglu":
        return jax.nn.silu(h)
    if activation in ("gelu", "geglu"):
        return jax.nn.gelu(h)
    raise ValueError(activation)


def block_f_for(d: int, f: int, n_weights: int, itemsize: int) -> int:
    """The F-block width: all of F where ``n_weights`` double-buffered
    (D, block_f) tiles fit :data:`WEIGHT_BUFFER_BYTES`, else the largest
    128-multiple dividing F that fits (128 at least)."""
    fits = lambda bf: 2 * n_weights * d * bf * itemsize <= WEIGHT_BUFFER_BYTES
    if fits(f) or f % 128:
        return f
    for bf in range(f - 128, 127, -128):
        if f % bf == 0 and fits(bf):
            return bf
    return 128


def _kernel(active_ref, n_ref, layer_ref, x_ref, g_ref, *refs,
            activation: str, gated: bool, n_f_blocks: int):
    if gated:
        wg_ref, wu_ref, wd_ref, o_ref, acc_scr = refs
    else:
        wu_ref, wd_ref, o_ref, acc_scr = refs
    s, fi = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (fi == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(s < n_ref[0])
    def _expert():
        x = x_ref[...]                                    # (N, D)
        up = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        if gated:
            h = _act(jnp.dot(x, wg_ref[0, 0],
                             preferred_element_type=jnp.float32),
                     activation) * up                     # (N, bf)
        else:
            h = _act(up, activation)
        y = jnp.dot(h.astype(wd_ref.dtype), wd_ref[0, 0],
                    preferred_element_type=jnp.float32)   # (N, D)
        acc_scr[...] += g_ref[0] * y                      # (N, 1) gates

    @pl.when((s == pl.num_programs(0) - 1) & (fi == n_f_blocks - 1))
    def _fin():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def routed_expert_ffn(x: jax.Array, combine: jax.Array, active: jax.Array,
                      n_active: jax.Array, layer: jax.Array,
                      w_gate: jax.Array | None, w_up: jax.Array,
                      w_down: jax.Array, *, activation: str = "swiglu",
                      interpret: bool = False) -> jax.Array:
    """x (N, D); combine (N, E) f32; active (E,) int32, the ``n_active``
    (1,) used experts first; layer (1,) int32; w_gate/w_up (L, E, D, F)
    (no w_gate: ungated ``act(x Wu) Wd``); w_down (L, E, F, D) -> (N, D)
    in x's dtype."""
    n, d = x.shape
    _, e, _, f = w_up.shape
    gated = w_gate is not None
    weights = (w_gate, w_up, w_down) if gated else (w_up, w_down)
    itemsize = jnp.dtype(w_up.dtype).itemsize
    bf = block_f_for(d, f, len(weights), itemsize)
    nfb = f // bf

    def expert(s, fi, active_ref, n_ref, layer_ref):
        """(layer, expert id, F block) of step (s, fi): past the last
        active slot, the last active expert's last block (no new DMA)."""
        live = s < n_ref[0]
        slot = jnp.where(live, s, jnp.maximum(n_ref[0] - 1, 0))
        return (layer_ref[0], active_ref[slot],
                jnp.where(live, fi, nfb - 1))

    def up_map(s, fi, *refs):
        li, ei, fb = expert(s, fi, *refs)
        return li, ei, 0, fb

    def down_map(s, fi, *refs):
        li, ei, fb = expert(s, fi, *refs)
        return li, ei, fb, 0

    def gate_map(s, fi, *refs):
        return expert(s, fi, *refs)[1], 0, 0

    whole = lambda s, fi, *_: (0, 0)
    in_specs = [pl.BlockSpec((n, d), whole),
                pl.BlockSpec((1, n, 1), gate_map)]
    in_specs += [pl.BlockSpec((1, 1, d, bf), up_map)] * (len(weights) - 1)
    in_specs.append(pl.BlockSpec((1, 1, bf, d), down_map))
    vmem = (2 * len(weights) * d * bf * itemsize + VMEM_HEADROOM_BYTES
            + 4 * n * d * 4)
    kernel = functools.partial(_kernel, activation=activation, gated=gated,
                               n_f_blocks=nfb)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(e, nfb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((n, d), whole),
            scratch_shapes=[pltpu.VMEM((n, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        # every step adds into the one accumulator
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="routed_expert_ffn",
    )(active.astype(jnp.int32), n_active.astype(jnp.int32),
      layer.astype(jnp.int32), x, combine.T.reshape(e, n, 1), *weights)
