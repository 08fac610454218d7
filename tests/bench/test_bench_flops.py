"""FLOP and byte counts of the benchmark (bench/flops.py) against hand
counts, and the peak table (bench/peaks.py)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, peaks  # noqa: E402


def _pair(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


# Mixtral-8x7B (2 of 32 layers) with a Mistral-7B v0.1 draft (4 of 32
# layers, sliding window 4096), at published widths: the shapes the counts
# are checked on, beside the configuration the benchmark runs.
_T7 = dict(arch_type="moe", n_layers=2, d_model=4096, n_heads=32,
           n_kv_heads=8, head_dim=128, d_ff=14336, vocab_size=32000,
           layer_pattern=["attn"], sliding_window=4096, n_experts=8,
           top_k=2)
P7 = {"target": _T7,
      "draft": dict(_T7, arch_type="dense", n_layers=4,
                    layer_pattern=["swa"], n_experts=0, top_k=0)}
P22 = _pair("mixtral-8x22b-v5e-pair")

# Hand counts.  Attention projections per layer: wq + wk + wv + wo =
# D*Hq*d + 2*D*Hkv*d + Hq*d*D.  SwiGLU FFN: 3*D*F per expert; a routed
# token passes top_k = 2 experts plus the D x E router.  LM head D*V.
# 8x7B target, 2 layers:
#   attn 4096*4096 + 2*4096*1024 + 4096*4096 = 41_943_040
#   ffn  2*3*4096*14336 + 4096*8 = 352_321_536 + 32_768 = 352_354_304
#   2 * (41_943_040 + 352_354_304) + 4096*32000 = 788_594_688
#   + 131_072_000 = 919_666_688
T7_ACTIVE = 919_666_688
# all 8 experts: 2 * (41_943_040 + 8*176_160_768 + 32_768) + 131_072_000
T7_ALL = 2 * (41_943_040 + 1_409_286_144 + 32_768) + 131_072_000
# Mistral-7B v0.1 draft, 4 layers: 4 * (41_943_040 + 176_160_768)
#   + 131_072_000 = 1_003_487_232
D7 = 1_003_487_232
# 8x22B target, 1 layer:
#   attn 6144*6144 + 2*6144*1024 + 6144*6144 = 88_080_384
#   ffn  2*3*6144*16384 + 6144*8 = 603_979_776 + 49_152 = 604_028_928
#   + head 6144*32768 = 201_326_592  ->  893_435_904
T22_ACTIVE = 893_435_904
# Mistral-7B v0.3 draft: 4 * 218_103_808 + 4096*32768 = 1_006_632_960
D22 = 1_006_632_960


@pytest.mark.parametrize("cfg,active,want", [
    (P7["target"], True, T7_ACTIVE), (P7["target"], False, T7_ALL),
    (P7["draft"], True, D7), (P22["target"], True, T22_ACTIVE),
    (P22["draft"], True, D22)])
def test_matmul_params_hand_counts(cfg, active, want):
    assert flops.matmul_params(cfg, active) == want


def test_fused_step_flops_hand_count_8x7b():
    # one verify slot at length 1000 (m = 5 rows at positions 995..999:
    # 996 + 997 + 998 + 999 + 1000 = 4990 keys), one draft slot fed at
    # position 600 (4 steps attending 601..604 keys = 2410 keys)
    qk_pv = 4 * 32 * 128                          # per key per query row
    want = (2 * T7_ACTIVE * 5 + 2 * qk_pv * 4990
            + 4 * 2 * D7 + 4 * qk_pv * 2410)
    got = flops.fused_step_flops(P7["target"], P7["draft"], 4, [1000],
                                 [600])
    assert got == want


def test_fused_step_flops_hand_count_8x22b():
    # GQA group 6: 48 query heads; one target layer, a full-attention
    # draft (no window)
    want = (2 * T22_ACTIVE * 5 + 1 * 4 * 48 * 128 * 4990
            + 4 * 2 * D22 + 4 * 4 * 32 * 128 * 2410)
    got = flops.fused_step_flops(P22["target"], P22["draft"], 4, [1000],
                                 [600])
    assert got == want


@pytest.mark.parametrize("pair,hq", [(P7, 32), (P22, 48)])
def test_paged_kernel_cost_hand_count(pair, hq):
    # two slots at lengths 1000 and 17, m = 5
    keys = 4990 + (13 + 14 + 15 + 16 + 17)
    want_flops = 4 * hq * 128 * keys
    # K and V of every live key once (8 kv heads x 128 x bf16), plus q in
    # and out (5 rows x hq heads x 128 x bf16 each way) per slot
    want_bytes = 2 * (1000 + 17) * 8 * 128 * 2 + 2 * (2 * 5 * hq * 128 * 2)
    assert flops.paged_attn_cost(pair["target"], 5, [1000, 17]) == \
        (want_flops, want_bytes)


def test_window_limits_keys():
    c = dict(P7["draft"], sliding_window=8)
    # rows at positions 10..14 each see at most 8 keys
    assert flops.attn_core_flops(c, 5, 15) == 4 * 32 * 128 * 8 * 5


def test_peak_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
