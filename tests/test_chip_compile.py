"""Compile the served path for a described (not attached) TPU v5e.

Nothing runs: these tests lower and compile at the published widths of
the Mixtral-8x7B / Mistral-7B smoke pair and of the Kimi-K2 / Moonlight
pair (``configs.PAIRS``) for one chip of a ``v5e:2x2`` topology, so a kernel tiling or a program size the chip
refuses fails here, with no chip.  The topology is described inside a
module fixture (only one process may load the TPU compiler), and the
tests skip where it cannot be described.
"""
import json
import math
import os
import re
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import model_config
from repro.configs import PAIRS
from repro.core.interleave import fused_verify_and_draft
from repro.core.spec_decode import tree_spec
from repro.kernels import ops
from repro.models import model as M
from repro.models.transformer import init_cache, init_paged_cache

ROOT = Path(__file__).resolve().parents[1]
TARGET, DRAFT = PAIRS["mixtral-8x7b-v5e-pair"]
K2, MOONLIGHT = PAIRS["kimi-k2-moonlight-v5e-pair"]
# the kimi-k2.offline cell: 16 slots a half, a 1568-token cache (98 blocks)
K2_BATCH, K2_MBS = 16, 98
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


def _pool_relayouts(hlo: str, pool_elems: int) -> list:
    """The compiled program's copies, transposes and fusions whose result
    holds as many elements as a KV pool: a relayout of the pool."""
    found = []
    for line in hlo.splitlines():
        op = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) "
                      r"(copy|copy-start|transpose|fusion)\(", line)
        if op and any(math.prod(map(int, dims.split(","))) >= pool_elems
                      for dims in re.findall(r"\[([\d,]+)\]", op[1])):
            found.append(line.strip()[:160])
    return found


def _compile_paged(sharding, b, hq, hkv, d, m, mbs, case):
    """Compile the paged kernel for ``b`` slots of ``mbs`` blocks each;
    return the compiled HLO and the element count of one pool."""
    nb = 1 + b * mbs
    pool_dt = jnp.int8 if case == "int8" else jnp.bfloat16
    pool = (nb, BLOCK, hkv, d)
    args = [jax.ShapeDtypeStruct((b, hq, m, d), jnp.bfloat16),
            jax.ShapeDtypeStruct(pool, pool_dt),
            jax.ShapeDtypeStruct(pool, pool_dt),
            jax.ShapeDtypeStruct((b, mbs), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32)]
    kw = {}
    if case == "int8":
        scales = jax.ShapeDtypeStruct(pool[:3] + (1,), jnp.float32)
        kw = {"k_scale": scales, "v_scale": scales}
    anc = (jnp.asarray(tree_spec((3, 2))["anc_bits"]) if case == "tree"
           else None)
    assert anc is None or anc.shape == (m,)

    def step(q, kp, vp, bt, lens, **scales):
        return ops.paged_decode_attention(q, kp, vp, bt, lens, anc_bits=anc,
                                          **scales)

    with ops.compiled_kernels(True):
        compiled = jax.jit(step).lower(*_on(sharding, args),
                                       **_on(sharding, kw)).compile()
    return compiled.as_text(), math.prod(pool)


@pytest.mark.parametrize("case", ["chain", "tree", "int8"])
def test_paged_kernel_compiles_at_published_widths(one_chip, case):
    m = 10 if case == "tree" else N_CAND + 1        # (3, 2) tree: 10 nodes
    hlo, pool = _compile_paged(one_chip, BATCH, TARGET.n_heads,
                               TARGET.n_kv_heads, TARGET.head_dim, m,
                               MAX_LEN // BLOCK, case)
    assert "tpu_custom_call" in hlo
    assert not _pool_relayouts(hlo, pool)


def test_paged_kernel_reads_pools_in_place_at_8x22b_cell_shapes(one_chip):
    """The ``mixtral-8x22b.offline`` cell's verify call: 16 slots of 98
    blocks, Mixtral-8x22B's 48 q / 8 kv heads of 128, m = 5, bf16 pools.
    The kernel reads the pools as stored: no copy of either."""
    hlo, pool = _compile_paged(one_chip, 16, 48, 8, 128, 5, 98, "chain")
    assert "tpu_custom_call" in hlo
    assert not _pool_relayouts(hlo, pool)


def _compile_fused(sharding, target, draft, batch, mbs):
    """The whole fused verify+draft step of a pair, from
    ``jax.eval_shape`` shapes, with the routing steered to the kernel;
    returns the compiled step and the weights' bytes."""
    key = jax.random.PRNGKey(0)
    tp = jax.eval_shape(partial(M.init_params, target), key)
    dp = jax.eval_shape(partial(M.init_params, draft), key)
    tcache = jax.eval_shape(lambda: init_paged_cache(
        target, batch, 1 + batch * mbs, BLOCK, mbs))
    dcache = jax.eval_shape(lambda: init_cache(draft, batch, mbs * BLOCK))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
    vstate = {"target_cache": tcache, "t_next": tok,
              "drafts": jax.ShapeDtypeStruct((batch, N_CAND), jnp.int32)}
    dstate = {"draft_cache": dcache, "t_next": tok}
    fused = jax.jit(fused_verify_and_draft,
                    static_argnames=("target_cfg", "draft_cfg", "n_cand",
                                     "mesh"))
    with ops.compiled_kernels(True):
        compiled = fused.lower(
            _on(sharding, tp), target, _on(sharding, dp), draft,
            _on(sharding, vstate), _on(sharding, dstate), N_CAND,
            None).compile()
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree.leaves((tp, dp)))
    return compiled, weights


def test_fused_step_compiles_and_fits_one_chip(one_chip):
    """The smoke pair's fused step compiles and fits one chip."""
    compiled, weights = _compile_fused(one_chip, TARGET, DRAFT, BATCH,
                                       MAX_LEN // BLOCK)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert weights < total < HBM_BYTES, (weights, total)


def test_latent_kernel_compiles_at_kimi_k2_cell_shapes(one_chip):
    """The ``kimi-k2.offline`` cell's verify call in latent mode: 16
    slots of 98 blocks, 64 heads x 5 rows against one 640-wide latent
    row a token (576 values and lane padding).  It reads the pool as
    stored: no copy of it."""
    rows = 640
    nb = 1 + K2_BATCH * K2_MBS
    args = [jax.ShapeDtypeStruct((K2_BATCH, 64, N_CAND + 1, rows),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((nb, BLOCK, 1, rows), jnp.bfloat16),
            jax.ShapeDtypeStruct((K2_BATCH, K2_MBS), jnp.int32),
            jax.ShapeDtypeStruct((K2_BATCH,), jnp.int32)]

    def step(q, pool, bt, lens):
        return ops.paged_mla_attention(q, pool, bt, lens, v_dim=512)

    with ops.compiled_kernels(True):
        hlo = jax.jit(step).lower(*_on(one_chip, args)).compile().as_text()
    assert "tpu_custom_call" in hlo and "%paged_mla_attention" in hlo
    assert not _pool_relayouts(hlo, nb * BLOCK * rows)


def test_kimi_pair_fused_step_compiles_and_fits_one_chip(one_chip):
    """The Kimi-K2 / Moonlight fused step at the cell's shapes: the
    latent kernel and the draft's routed expert FFN are in it, and
    weights, caches and temporaries fit 16 GB with room for the second
    half's caches."""
    compiled, weights = _compile_fused(one_chip, K2, MOONLIGHT, K2_BATCH,
                                       K2_MBS)
    hlo = compiled.as_text()
    assert "%paged_mla_attention" in hlo and "%routed_expert_ffn" in hlo
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert weights < total < 0.9 * HBM_BYTES, (weights, total)


# HLO instructions of the 8x22B cell's fused step, which has no routed
# expert FFN: its target verifies 80 tokens over 8 experts (dense path),
# its draft is dense.  A different count means that program changed.
MIXTRAL_8X22B_FUSED_OPS = 4828


def test_8x22b_fused_step_keeps_the_dense_expert_path(one_chip):
    """The ``mixtral-8x22b.offline`` cell's fused step at its shapes (16
    slots of 98 blocks) holds no routed expert FFN and keeps its op
    count."""
    doc = json.loads((ROOT / "bench/configs/mixtral-8x22b-v5e-pair.json")
                     .read_text())
    target, draft = (model_config(doc[k]) for k in ("target", "draft"))
    compiled, _ = _compile_fused(one_chip, target, draft, 16, 98)
    hlo = compiled.as_text()
    ops_ = [l for l in hlo.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", l)]
    assert "%routed_expert_ffn" not in hlo
    assert len(ops_) == MIXTRAL_8X22B_FUSED_OPS
