"""What decides ``correct``: served tokens and draft proposals of the
timed window, compared with the plain reference (``bench/reference.py``).

Once the window has closed, a sample drawn from the seed of the requests
it finished, the longest among them, is run through the reference over
each padded prompt and its served tokens (the engine left-pads prompts to
its length bucket and attends the pads, so the padded prompt is the
context).  Two numbers are compared:

* ``target_settled_gap``: the widest gap by which a served token's
  reference logit lies below the reference's best at that position, over
  the served tokens whose routing the reference settles (``SETTLED``).
  It covers the admission prefill (a request's first token) and every
  fused verify after it.  ``target_gap``, over every served token, is
  logged beside it.
* ``draft_gap``: the same gap for the draft's proposals under the
  reference draft model, at every round in which a sampled request was
  verified (the first proposal, against the committed sequence) and, for
  a few rounds drawn from the seed, for every proposal of the chain.

With ``control=True`` it also reads, at the same positions, the gap of the
token that the int8 reference puts first (``control_target_gap``,
``control_draft_gap``): the reading a lower-precision path would give.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import match_slot
from bench.traffic import padded

ROOT = Path(__file__).resolve().parents[1]

SEQ_BUCKET = 256        # reference sequences are end-padded to this multiple
# A served token is "settled" when, in every MoE layer, the reference's
# last chosen gate beats the first unchosen one by this much.  Below it,
# bfloat16 rounding can route the token to another expert, which moves
# its logits by O(1): the widest gap over all tokens is set by such flips
# (measured on a TPU v5e), so ``target_settled_gap`` reads the others.
SETTLED = 0.01
N_SAMPLE = 6            # requests per run, the longest included
N_FULL_ROUNDS = 3       # rounds per request whose whole draft chain is read


def _draft_rounds(obs, rid: int) -> list:
    """(tokens served before the round, round, slot) for each fused round
    that verified request ``rid`` and whose slot its output row names."""
    out = []
    for rnd in obs.rounds:
        mine = [(k, tok) for r, k, tok in rnd.emits if r == rid]
        if not mine or rnd.drafts is None:
            continue
        slot = match_slot(rnd, [tok for _, tok in mine])
        if slot is not None:
            out.append((mine[0][0], rnd, slot))
    return out


def sample(finished: list, obs, by_rid: dict, bucket, seed: int) -> list:
    """The requests the check reads, with their draft proposals."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 2])
    longest = max(finished, key=lambda r: (len(obs.tokens[r]), -r))
    rest = sorted(set(finished) - {longest})
    pick = [longest] + [int(x) for x in rng.choice(
        rest, size=min(N_SAMPLE - 1, len(rest)), replace=False)]
    out = []
    for rid in pick:
        rounds = _draft_rounds(obs, rid)
        full = set(rng.choice(len(rounds), size=min(N_FULL_ROUNDS,
                                                    len(rounds)),
                              replace=False).tolist()) if rounds else set()
        drafts = jax.device_get([rnd.drafts for _, rnd, _ in rounds])
        out.append({"rid": rid,
                    "prompt": padded(by_rid[rid].prompt, bucket),
                    "served": np.asarray(obs.tokens[rid], np.int32),
                    "drafts": [(k, np.asarray(d)[slot], i in full)
                               for i, ((k, _, slot), d)
                               in enumerate(zip(rounds, drafts))]})
    return out


def reference_module(doc: dict):
    """The plain reference the configuration names (``reference``: a
    path under the checkout, by default ``bench/reference.py``); it
    provides ``logits(params, cfg, tokens, quant=None)``."""
    path = ROOT / doc.get("reference", "bench/reference.py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seq_len(mix: dict) -> int:
    """The one length every reference sequence of a cell is padded to:
    the longest padded prompt, output and draft chain a run can hold."""
    b = mix["serving"]["length_bucket"]
    n = (-(-mix["prompt_len"]["max"] // b) * b + mix["output_len"]["max"]
         + mix["serving"]["n_cand"])
    return -(-n // SEQ_BUCKET) * SEQ_BUCKET


def _pad(x, n: int, fill: int = 0) -> np.ndarray:
    out = np.full(n, fill, np.int32)
    out[:len(x)] = x
    return out


@jax.jit
def _gaps(ref, rows, toks):
    """max(ref[row]) - ref[row, tok]; rows < 0 are padding."""
    r = ref[jnp.maximum(rows, 0)]
    g = r.max(-1) - jnp.take_along_axis(
        r, jnp.maximum(toks, 0)[:, None], 1)[:, 0]
    return jnp.where(rows >= 0, g, jnp.nan)


@jax.jit
def _argmax_rows(ref, rows):
    return jnp.argmax(ref[jnp.maximum(rows, 0)], -1).astype(jnp.int32)


def compare(tp, dp, doc: dict, samples: list, length: int,
            control: bool = False) -> dict:
    """The readings over ``samples``, every sequence padded to
    ``length``: the widest and the mean gap of the served tokens
    (``target_*``) and of the draft proposals (``draft_*``), the share of
    served tokens that are not the reference's best, and per-token arrays
    for a closer look (``arrays``).  With ``control``, the same readings
    for the token the int8 reference puts first (``control_*``)."""
    ref_mod = reference_module(doc)
    acc = {k: [] for k in ("target", "draft", "control_target",
                           "control_draft", "margin")}

    def read(params, cfg, seq, rows, toks, what):
        n = len(rows)
        seq, rows_p = _pad(seq, length), _pad(rows, length, -1)
        ref, margin = ref_mod.logits_and_margins(params, cfg, seq)
        acc[what].append(np.asarray(_gaps(ref, rows_p, _pad(toks, length)))
                         [:n])
        if what == "target":
            acc["margin"].append(np.asarray(margin)[np.asarray(rows)])
        if control:
            ctrl = ref_mod.logits(params, cfg, seq, "int8")
            acc["control_" + what].append(np.asarray(_gaps(
                ref, rows_p, _argmax_rows(ctrl, rows_p)))[:n])

    for s in samples:
        p, served = s["prompt"], s["served"]
        seq = np.concatenate([p, served])
        read(tp, doc["target"], seq[:-1],
             np.arange(len(p) - 1, len(seq) - 1), served, "target")
        if not s["drafts"]:
            continue
        read(dp, doc["draft"], seq,
             [len(p) + k - 1 for k, _, _ in s["drafts"]],
             [c[0] for _, c, _ in s["drafts"]], "draft")
        for k, cands, full in s["drafts"]:
            if full:
                chain = np.concatenate([p, served[:k], cands[:-1]])
                read(dp, doc["draft"], chain,
                     np.arange(len(p) + k - 1, len(chain)), cands, "draft")
    arrays = {k: np.concatenate(v) for k, v in acc.items() if v}
    if "margin" in arrays:
        settled = arrays["margin"] >= SETTLED
        for k in ("target", "control_target"):
            if k in arrays:
                arrays[k.replace("target", "target_settled")] = \
                    arrays[k][settled]
    out = {"arrays": arrays}
    for k, v in arrays.items():
        if k != "margin" and v.size:
            out[k + "_gap"] = float(v.max())
            out[k + "_mean_gap"] = float(v.mean())
            out[k + "_mismatch"] = float((v > 0).mean())
            out["n_" + k] = int(v.size)
    return out


def log_readings(readings: dict, log):
    """Every reading on stderr, and the served tokens with the widest
    gaps beside the reference's routing margin there."""
    for k, v in sorted(readings.items()):
        if k != "arrays":
            log(f"reading {k}: {v}")
    a = readings["arrays"]
    if "target" in a and "margin" in a:
        top = np.argsort(-a["target"])[:5]
        log("widest target gaps (gap, routing margin): " + ", ".join(
            f"({a['target'][i]:.4f}, {a['margin'][i]:.5f})" for i in top))
        near = a["margin"] < SETTLED
        near_gap = a["target"][near].mean() if near.any() else 0.0
        log(f"served tokens with routing margin < {SETTLED}: "
            f"{near.mean():.4f}; their mean gap {near_gap:.4f}, the "
            f"others' {a['target'][~near].mean():.4f}")
