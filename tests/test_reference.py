"""The float32 reference forward, and the served path checked against it.

``models/reference.py`` is written from the model's definition; at
float32 the model's own forward, and one prefill plus one paged decode
step, must match it closely.  ``chip_smoke.py`` runs the same paged
check at published widths on the chip.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import PAIRS
from repro.core.pipeline import SpecOffloadEngine
from repro.models import model as M
from repro.models.reference import reference_logits

from conftest import tiny_config, tiny_draft_config

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

CONFIGS = {
    "moe_target": PAIRS["mixtral-8x7b-v5e-pair"][0].reduced(d_model=64),
    "swa_draft": tiny_draft_config(),
    "dense_attn": tiny_config(("attn",)),
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_matches_forward(name):
    cfg = CONFIGS[name]
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (20,), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = reference_logits(p, cfg, toks)
        got = M.forward_train(p, cfg, {"tokens": toks[None]})[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", ["moe_target", "dense_attn"])
def test_prefill_paged_decode_matches_reference(name):
    """chip_smoke's logits check at float32: prefill + one paged decode
    step agree with the reference (the chip's bound is for bf16)."""
    cfg = CONFIGS[name]
    p = M.init_params(cfg, jax.random.PRNGKey(2))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 37)
    with jax.default_matmul_precision("highest"):
        err = chip_smoke.logits_check(p, cfg, prompt.astype(np.int32), 8)
    assert err["rel_l2"] < 1e-4 and err["max_abs"] < 1e-3, err


def test_paged_kernel_check_passes_in_interpret_mode():
    cfg = CONFIGS["moe_target"]
    assert chip_smoke.paged_kernel_check(cfg, batch=2, m=3, mbs=4,
                                         block_size=8, seed=0) < 0.01


def test_reference_rejects_uncovered_layers():
    cfg = tiny_config(("rglru",))
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        reference_logits(p, cfg, np.arange(4))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["moe_target", "swa_draft"])
def test_jitted_init_matches_eager(name, dtype):
    """init_from_seed builds weights under jit; a seed must give the same
    weights, bit for bit, as eager M.init_params."""
    cfg = dataclasses.replace(CONFIGS[name], dtype=dtype)
    eng = SpecOffloadEngine(cfg, cfg)
    eng.init_from_seed(3)
    k1, _ = jax.random.split(jax.random.PRNGKey(3))
    eager = M.init_params(cfg, k1)
    for a, b in zip(jax.tree.leaves(eng.tp), jax.tree.leaves(eager)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
