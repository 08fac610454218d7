"""One generator for every traffic mix: reads ``bench/traffic/<mix>.json``.

Every seed gets the same multiset of sizes, in another order:
lengths are the distribution's quantiles at ``(i + 0.5) / n`` and the seed
shuffles them, so two seeds differ in which request comes when and in the
token ids, not in how much work there is.  One kind of mix so far,
``closed``: a backlog of requests, all due at once; the harness keeps a
fixed number outstanding and replaces each finished one with the next.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # int32 token ids, unpadded
    max_new_tokens: int


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a length distribution, clipped and
    rounded to whole tokens."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def max_sizes(mix: dict) -> tuple:
    """(longest prompt, longest output) any seed can draw."""
    return mix["prompt_len"]["max"], mix["output_len"]["max"]


def generate(mix: dict, seed: int, vocab: int) -> list:
    """The requests of one run.  Token ids are drawn from ``[1, vocab)``
    (0 is the engine's pad id)."""
    if mix["kind"] != "closed":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    n = int(mix["n_requests"])
    rng = np.random.default_rng(seed)
    plens = rng.permutation(_quantiles(mix["prompt_len"], n))
    olens = rng.permutation(_quantiles(mix["output_len"], n))
    return [Request(rid=i,
                    prompt=rng.integers(1, vocab, int(p)).astype(np.int32),
                    max_new_tokens=int(o))
            for i, (p, o) in enumerate(zip(plens, olens))]


def padded(prompt: np.ndarray, bucket: int | None, pad_id: int = 0):
    """The prompt as the engine prefills it: left-padded with ``pad_id``
    up to a multiple of ``bucket`` (the engine's ``length_bucket``)."""
    if not bucket:
        return np.asarray(prompt, np.int32)
    n = math.ceil(len(prompt) / bucket) * bucket
    return np.concatenate([np.full(n - len(prompt), pad_id, np.int32),
                           np.asarray(prompt, np.int32)])
