"""Compile the served path for a described (not attached) TPU v5e.

Nothing runs: these tests lower and compile at the published widths of
the Mixtral-8x7B / Mistral-7B smoke pair (``configs.PAIRS``) for one chip
of a ``v5e:2x2`` topology, so a kernel tiling or a program size the chip
refuses fails here, with no chip.  The topology is described inside a
module fixture (only one process may load the TPU compiler), and the
tests skip where it cannot be described.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import PAIRS
from repro.core.interleave import fused_verify_and_draft
from repro.core.spec_decode import tree_spec
from repro.kernels import ops
from repro.models import model as M
from repro.models.transformer import init_cache, init_paged_cache

TARGET, DRAFT = PAIRS["mixtral-8x7b-v5e-pair"]
BATCH, N_CAND, MAX_LEN, BLOCK = 8, 4, 2048, 16
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent
    # cache but never read back here: keep it off for these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("case", ["chain", "tree", "int8"])
def test_paged_kernel_compiles_at_published_widths(one_chip, case):
    tree = case == "tree"
    m = 10 if tree else N_CAND + 1                  # (3, 2) tree: 10 nodes
    mbs = MAX_LEN // BLOCK
    nb = 1 + BATCH * mbs
    pool_dt = jnp.int8 if case == "int8" else jnp.bfloat16
    pool = (nb, BLOCK, TARGET.n_kv_heads, TARGET.head_dim)
    args = [jax.ShapeDtypeStruct((BATCH, TARGET.n_heads, m, TARGET.head_dim),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct(pool, pool_dt),
            jax.ShapeDtypeStruct(pool, pool_dt),
            jax.ShapeDtypeStruct((BATCH, mbs), jnp.int32),
            jax.ShapeDtypeStruct((BATCH,), jnp.int32)]
    kw = {}
    if case == "int8":
        scales = jax.ShapeDtypeStruct(pool[:3] + (1,), jnp.float32)
        kw = {"k_scale": scales, "v_scale": scales}
    anc = (jnp.asarray(tree_spec((3, 2))["anc_bits"]) if tree else None)
    assert anc is None or anc.shape == (m,)

    def step(q, kp, vp, bt, lens, **scales):
        return ops.paged_decode_attention(q, kp, vp, bt, lens, anc_bits=anc,
                                          **scales)

    with ops.compiled_kernels(True):
        compiled = jax.jit(step).lower(*_on(one_chip, args),
                                       **_on(one_chip, kw)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_compiles_and_fits_one_chip(one_chip):
    """The whole fused verify+draft step of the smoke pair, from
    ``jax.eval_shape`` shapes, with the routing steered to the kernel."""
    key = jax.random.PRNGKey(0)
    tp = jax.eval_shape(partial(M.init_params, TARGET), key)
    dp = jax.eval_shape(partial(M.init_params, DRAFT), key)
    mbs = MAX_LEN // BLOCK
    tcache = jax.eval_shape(lambda: init_paged_cache(
        TARGET, BATCH, 1 + BATCH * mbs, BLOCK, mbs))
    dcache = jax.eval_shape(lambda: init_cache(DRAFT, BATCH, MAX_LEN))
    tok = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    vstate = {"target_cache": tcache, "t_next": tok,
              "drafts": jax.ShapeDtypeStruct((BATCH, N_CAND), jnp.int32)}
    dstate = {"draft_cache": dcache, "t_next": tok}
    fused = jax.jit(fused_verify_and_draft,
                    static_argnames=("target_cfg", "draft_cfg", "n_cand",
                                     "mesh"))
    with ops.compiled_kernels(True):
        compiled = fused.lower(
            _on(one_chip, tp), TARGET, _on(one_chip, dp), DRAFT,
            _on(one_chip, vstate), _on(one_chip, dstate), N_CAND,
            None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree.leaves((tp, dp)))
    assert weights < total < HBM_BYTES, (weights, total)
