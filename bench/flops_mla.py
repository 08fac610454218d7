"""Operations and bytes of the latent-mode paged verify kernel (MLA's
absorbed attention), from the configuration's shapes.

As in ``bench/flops.py``, these count the work the algorithm requires:
live slots only, each of the ``m`` query rows of every head attending the
keys before it (causal), each key's latent row read once at its
published width (``kv_lora_rank + qk_rope_head_dim``), not the kernel's
lane-padded row.  A configuration is the dict of a ``target``/``draft``
entry of a file in ``bench/configs``.
"""
from __future__ import annotations


def latent_row(c: dict) -> int:
    """Values of one token's latent row: ``c_kv ‖ k_pe``."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def keys_attended(m: int, length: int) -> int:
    """Keys the last ``m`` of ``length`` positions attend, summed."""
    return sum(length - m + i + 1 for i in range(m))


def mla_attn_cost(c: dict, m: int, lengths, kv_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) one latent verify-attention call needs for one
    layer: per head and key, the score over the latent row (2 x row) and
    the value product over ``c_kv`` (2 x kv_lora_rank); bytes are every
    live slot's latent rows once, the absorbed queries in and the
    attended latents out (bf16)."""
    lengths = list(lengths)
    h, row, c_kv = c["n_heads"], latent_row(c), c["kv_lora_rank"]
    flops = sum(2 * h * (row + c_kv) * keys_attended(m, n) for n in lengths)
    kv = sum(n * row * kv_bytes for n in lengths)
    qo = len(lengths) * m * h * (row + c_kv) * 2
    return flops, kv + qo


def mla_layers(c: dict) -> int:
    return c["n_layers"]
