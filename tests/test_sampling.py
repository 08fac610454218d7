"""Sampling-mode speculative decoding (Leviathan rule) and CLI launchers."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spec_decode import sampled_acceptance, spec_round
from repro.models import model as M
from repro.models.transformer import init_cache

from conftest import tiny_config, tiny_draft_config

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_spec_round_sampling_mode_runs(jitted):
    tcfg = tiny_config(("attn",))
    dcfg = tiny_draft_config()
    tp = M.init_params(tcfg, jax.random.PRNGKey(1))
    dp = M.init_params(dcfg, jax.random.PRNGKey(2))
    B, L, m = 4, 8, 3
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, L), 0, 61)
    tc = init_cache(tcfg, B, 64)
    dc = init_cache(dcfg, B, 64)
    lg, tc = jitted["prefill"](tp, tcfg, toks, tc)
    _, dc = jitted["prefill"](dp, dcfg, toks, dc)
    r = spec_round(tp, tcfg, tc, dp, dcfg, dc, jnp.argmax(lg, -1), m,
                   key=jax.random.PRNGKey(7), sample=True)
    ne = np.asarray(r["n_emitted"])
    assert ((ne >= 1) & (ne <= m + 1)).all()
    assert (np.asarray(r["tokens"]) < tcfg.vocab_size).all()


def test_sampled_acceptance_identical_distributions_accept_all():
    """p_draft == p_target => acceptance prob 1 per token."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (256, 5, 32)) * 3
    # drafts sampled from the target distribution itself
    drafts = jax.vmap(
        lambda lg, k: jax.random.categorical(k, lg[:4]),
        in_axes=(0, 0))(logits, jax.random.split(key, 256))
    a, nxt, nc = sampled_acceptance(drafts, logits[:, :4], logits,
                                    jax.random.PRNGKey(1))
    assert float(a.mean()) > 3.3       # ~4.0 expected, allow slack


def test_sampled_acceptance_disjoint_distributions_reject():
    """Draft puts mass where the target has none -> near-total rejection,
    and resampled tokens come from the target's support."""
    b, m, v = 128, 4, 16
    tl = jnp.full((b, m + 1, v), -30.0).at[:, :, :4].set(5.0)   # target: 0-3
    dl = jnp.full((b, m, v), -30.0).at[:, :, 8:12].set(5.0)     # draft: 8-11
    drafts = jnp.full((b, m), 9, jnp.int32)
    a, nxt, nc = sampled_acceptance(drafts, dl, tl, jax.random.PRNGKey(0))
    assert float(a.mean()) < 0.1
    assert (np.asarray(nxt) < 4).all()


def _cli(args):
    r = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                       text=True, timeout=560,
                       env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                            "HOME": os.environ.get("HOME", ""),
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    return r.stdout


def test_serve_launcher_plan():
    out = _cli(["repro.launch.serve", "--arch", "mixtral-8x7b", "--plan",
                "--prompt-len", "300", "--gen", "32"])
    assert "policy" in out and "placement" in out


def test_train_launcher_production_plan():
    out = _cli(["repro.launch.train", "--arch", "llama3-405b",
                "--production-plan"])
    assert "adafactor" in out and "405" in out
