"""Operations and bytes the serving step needs, from the model's shapes.

These count the work the algorithm requires, not what a kernel happens to
do: live slots only, the experts a token is routed to only (top-k, no
dropless padding), and each query row attending the keys before it.  A
configuration is the dict of a ``target``/``draft`` entry of a file in
``bench/configs``.
"""
from __future__ import annotations


def _attn_proj(c: dict) -> int:
    d_model, hd = c["d_model"], c["head_dim"]
    return (d_model * c["n_heads"] * hd + 2 * d_model * c["n_kv_heads"] * hd
            + c["n_heads"] * hd * d_model)


def _ffn(c: dict, active: bool) -> int:
    dense = 3 * c["d_model"] * c["d_ff"]          # SwiGLU: gate, up, down
    if not c.get("n_experts"):
        return dense
    experts = c["top_k"] if active else c["n_experts"]
    return experts * dense + c["d_model"] * c["n_experts"]   # + router


def matmul_params(c: dict, active: bool = True) -> int:
    """Weights one token is multiplied through: every layer's attention
    projections and FFN (the routed ``top_k`` experts when ``active``)
    and the LM head; the embedding is a lookup and does not count."""
    per_layer = _attn_proj(c) + _ffn(c, active)
    return c["n_layers"] * per_layer + c["d_model"] * c["vocab_size"]


def _keys(c: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends (window-limited)."""
    n = pos + 1
    if "swa" in c["layer_pattern"]:
        n = min(n, c["sliding_window"])
    return n


def attn_core_flops(c: dict, m: int, length: int) -> int:
    """QK and PV FLOPs of one layer for ``m`` query rows that are the last
    ``m`` of ``length`` positions (causal)."""
    keys = sum(_keys(c, length - m + i) for i in range(m))
    return 4 * c["n_heads"] * c["head_dim"] * keys


def attn_layers(c: dict) -> int:
    return c["n_layers"]


def verify_flops(c: dict, m: int, length: int) -> int:
    """One slot's target verify of ``m`` tokens ending at ``length``."""
    return (2 * matmul_params(c, active=True) * m
            + attn_layers(c) * attn_core_flops(c, m, length))


def draft_flops(c: dict, n_cand: int, pos: int) -> int:
    """One slot's ``n_cand`` draft proposals, the first fed at ``pos``."""
    return sum(2 * matmul_params(c) + attn_layers(c)
               * attn_core_flops(c, 1, pos + j + 1) for j in range(n_cand))


def fused_step_flops(target: dict, draft: dict, n_cand: int,
                     verify_lengths, draft_positions) -> int:
    """Useful FLOPs of one fused round: the target verifies ``n_cand + 1``
    tokens for each live slot of one half (``verify_lengths``: cache
    length after the round's tokens are written), the draft proposes
    ``n_cand`` tokens for each live slot of the other (``draft_positions``:
    the position of the first token it is fed)."""
    m = n_cand + 1
    return (sum(verify_flops(target, m, n) for n in verify_lengths)
            + sum(draft_flops(draft, n_cand, p) for p in draft_positions))


def paged_attn_cost(c: dict, m: int, lengths, kv_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) one paged verify-attention call needs for one layer:
    ``m`` query rows per live slot against ``lengths`` keys (bf16 q, k, v
    and output); the least bytes read every key and value once."""
    lengths = list(lengths)
    flops = sum(attn_core_flops(c, m, n) for n in lengths)
    hkv, hq, hd = c["n_kv_heads"], c["n_heads"], c["head_dim"]
    kv = sum(2 * n * hkv * hd * kv_bytes for n in lengths)
    qo = len(lengths) * 2 * m * hq * hd * 2
    return flops, kv + qo
