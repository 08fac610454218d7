"""Architecture registry: the 10 assigned architectures (``--arch <id>``)
plus the SpecOffload paper's own models."""
from repro.configs import base
from repro.configs.base import (INPUT_SHAPES, MISTRAL_7B, MIXTRAL_8X7B,
                                MIXTRAL_8X22B, InputShape, ModelConfig)
from repro.configs.chameleon_34b import CONFIG as CHAMELEON_34B
from repro.configs.gemma3_12b import CONFIG as GEMMA3_12B
from repro.configs.kimi_k2_v5e_pair import DRAFT as MOONLIGHT_5L
from repro.configs.kimi_k2_v5e_pair import TARGET as KIMI_K2_5L
from repro.configs.llama3_405b import CONFIG as LLAMA3_405B
from repro.configs.llama4_maverick_400b import CONFIG as LLAMA4_MAVERICK
from repro.configs.mixtral_8x7b_v5e_pair import DRAFT as MISTRAL_7B_4L
from repro.configs.mixtral_8x7b_v5e_pair import TARGET as MIXTRAL_8X7B_2L
from repro.configs.phi3_medium_14b import CONFIG as PHI3_MEDIUM
from repro.configs.phi35_moe_42b import CONFIG as PHI35_MOE
from repro.configs.recurrentgemma_2b import CONFIG as RECURRENTGEMMA_2B
from repro.configs.rwkv6_7b import CONFIG as RWKV6_7B
from repro.configs.starcoder2_7b import CONFIG as STARCODER2_7B
from repro.configs.whisper_base import CONFIG as WHISPER_BASE

# The assigned pool (``--arch`` ids).
ARCHS = {
    "chameleon-34b": CHAMELEON_34B,
    "phi3.5-moe-42b-a6.6b": PHI35_MOE,
    "phi3-medium-14b": PHI3_MEDIUM,
    "recurrentgemma-2b": RECURRENTGEMMA_2B,
    "llama3-405b": LLAMA3_405B,
    "whisper-base": WHISPER_BASE,
    "llama4-maverick-400b-a17b": LLAMA4_MAVERICK,
    "gemma3-12b": GEMMA3_12B,
    "rwkv6-7b": RWKV6_7B,
    "starcoder2-7b": STARCODER2_7B,
}

# The paper's own models (offload engine + benchmarks).
PAPER_MODELS = {
    "mixtral-8x7b": MIXTRAL_8X7B,
    "mixtral-8x22b": MIXTRAL_8X22B,
    "mistral-7b": MISTRAL_7B,
    "mixtral-8x7b-2l": MIXTRAL_8X7B_2L,
    "mistral-7b-4l": MISTRAL_7B_4L,
    "kimi-k2-5l": KIMI_K2_5L,
    "moonlight-16b-a3b-5l": MOONLIGHT_5L,
}

# Target/draft pairs served as they are, at published widths (no
# ``.reduced()``); see repro/configs/mixtral_8x7b_v5e_pair.py and
# repro/configs/kimi_k2_v5e_pair.py.
PAIRS = {
    "mixtral-8x7b-v5e-pair": (MIXTRAL_8X7B_2L, MISTRAL_7B_4L),
    "kimi-k2-moonlight-v5e-pair": (KIMI_K2_5L, MOONLIGHT_5L),
}

ALL_CONFIGS = {**ARCHS, **PAPER_MODELS}


def get_config(name: str) -> ModelConfig:
    try:
        return ALL_CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ALL_CONFIGS)}")


__all__ = ["ARCHS", "PAPER_MODELS", "PAIRS", "ALL_CONFIGS", "get_config",
           "ModelConfig", "InputShape", "INPUT_SHAPES", "base"]
