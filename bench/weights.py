"""Random weights from the seed, made on the device in one jitted call.

The program's own ``init_params`` is traced with ``jax.eval_shape`` only,
to learn the names, shapes and dtypes of its parameter tree; every value
is made here, so the reference (``bench/reference.py``) checks the
program against weights the program did not make.  Rules by leaf name:
norm ``scale`` is one, the token embedding ``tok`` is N(0, 0.02), every
other leaf is a truncated normal (+-3) times ``fan_in ** -0.5``, with
``fan_in`` the second-to-last dimension.  Large leaves are drawn slice by
slice along their leading axes, so float32 temporaries never exceed one
matrix.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SLICE_BYTES = 64 << 20          # draw leaves above this one matrix at a time


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _draw(key, shape: tuple, dtype, name: str):
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "tok":
        draw = lambda k, s: 0.02 * jax.random.normal(k, s, jnp.float32)
    else:
        std = shape[-2] ** -0.5
        draw = lambda k, s: std * jax.random.truncated_normal(
            k, -3.0, 3.0, s, jnp.float32)
    n_lead = 0
    while (n_lead < len(shape) - 2 and
           4 * math.prod(shape[n_lead:]) > SLICE_BYTES):
        n_lead += 1
    if n_lead == 0:
        return draw(key, shape).astype(dtype)
    lead, inner = shape[:n_lead], shape[n_lead:]
    keys = jax.random.split(key, math.prod(lead))
    out = jax.lax.map(lambda k: draw(k, inner).astype(dtype), keys)
    return out.reshape(shape)


def make_params(shapes, key):
    """Fill the tree of ``jax.ShapeDtypeStruct`` ``shapes`` from ``key``."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)
    keys = jax.random.split(key, len(leaves))
    vals = [_draw(k, s.shape, s.dtype, _leaf_name(p))
            for k, (p, s) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (beyond 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def build(init_fn, target_cfg, draft_cfg, seed: int):
    """(target_params, draft_params) for ``seed`` in one jitted call;
    ``init_fn(cfg, key)`` is the program's initializer, used for shapes
    only."""
    k = jax.random.PRNGKey(0)
    t_shapes = jax.eval_shape(lambda k: init_fn(target_cfg, k), k)
    d_shapes = jax.eval_shape(lambda k: init_fn(draft_cfg, k), k)

    def both(key):
        kt, kd = jax.random.split(key)
        return make_params(t_shapes, kt), make_params(d_shapes, kd)

    return jax.jit(both)(seed_key(seed))
