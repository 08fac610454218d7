"""Model / run configuration for the repro framework.

Every assigned architecture gets a ``ModelConfig`` in its own module under
``repro.configs``; the SpecOffload paper's own models (Mixtral 8x7B/8x22B,
Mistral 7B draft) live here too.  Configs are frozen dataclasses so they can
be hashed into jit static args.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


# Layer kinds usable in ``layer_pattern``.
ATTN = "attn"      # global (full, causal) attention
SWA = "swa"        # sliding-window (local) attention
RGLRU = "rglru"    # RG-LRU recurrent block (Griffin / RecurrentGemma)
RWKV = "rwkv"      # RWKV-6 time-mix block (attention-free)
MLA = "mla"        # multi-head latent attention (DeepSeek-V2/V3 block)

LAYER_KINDS = (ATTN, SWA, RGLRU, RWKV, MLA)
ROUTERS = ("softmax", "sigmoid_bias")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters + framework knobs.

    ``layer_pattern`` is the repeating *layer group*; the model has
    ``n_layers / len(layer_pattern)`` groups and the forward pass is a
    ``lax.scan`` over groups (compile-time friendly for 126-layer models).
    """

    name: str
    arch_type: str                       # dense|moe|hybrid|ssm|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                    # 0 -> d_model // n_heads
    layer_pattern: tuple = (ATTN,)
    sliding_window: int = 4096
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 2.0
    # dropless dispatch (capacity = n_tokens): exact but memory-heavy; used
    # for decode phases and correctness tests, not for large-batch prefill
    moe_dropless: bool = False
    # which layer_pattern positions use the MoE FFN (None -> all, when moe);
    # e.g. llama4-maverick interleaves dense and MoE layers 1:1
    moe_pattern: tuple = ()
    # expert width (0 -> d_ff); the leading dense layers and non-MoE
    # pattern positions keep d_ff
    expert_d_ff: int = 0
    # experts every token passes through besides its routed ones, held
    # as one MLP of width n_shared_experts * expert_d_ff
    n_shared_experts: int = 0
    # the router's published width (0 -> n_experts).  This model holds
    # routed experts [expert_offset, expert_offset + n_experts) of them:
    # one chip's share under expert parallelism
    router_experts: int = 0
    expert_offset: int = 0
    # 'softmax': softmax gates, top-k renormalized (Mixtral);
    # 'sigmoid_bias': sigmoid scores, top-k chosen on score plus a learned
    # per-expert correction bias, gates the unbiased scores renormalized
    # over the chosen (DeepSeek-V3 noaux_tc with one group, norm_topk_prob)
    router: str = "softmax"
    routed_scaling_factor: float = 1.0
    # leading layers with a dense FFN of width d_ff, outside the scan over
    # MoE groups (DeepSeek-V3 first_k_dense_replace)
    first_dense_layers: int = 0
    # latent attention (MLA): q_lora_rank 0 projects q from x directly
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary for MLA, as the published config's rope_scaling mapping
    # (kept as sorted (key, value) pairs so the config stays hashable)
    rope_scaling: tuple = ()
    # positional / misc
    rope_theta: float = 10_000.0
    use_rope: bool = True
    norm: str = "rmsnorm"                # rmsnorm|layernorm
    norm_eps: float = 1e-6
    activation: str = "swiglu"           # swiglu|gelu|geglu
    tie_embeddings: bool = False
    # encoder-decoder (whisper)
    encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_len: int = 1500              # stub frontend frames
    # recurrent (RG-LRU)
    rnn_width: int = 0                   # 0 -> d_model
    conv_width: int = 4
    # RWKV
    rwkv_head_size: int = 64
    # numerics / compile
    dtype: str = "bfloat16"
    # KV-cache storage dtype for full-attention layers: 'bfloat16' or
    # 'int8' (per-row-per-head absmax quantization; halves the
    # memory-dominant decode working set — EXPERIMENTS.md §Perf).
    # Sliding-window ring caches stay bf16 (they are small by design).
    kv_cache_dtype: str = "bfloat16"
    remat: bool = True
    # offload the per-layer-group residual carry to pinned host memory
    # during training (ZeRO-R-style; the paper's offload tier applied to
    # the training substrate).  Falls back to sqrt-remat when False.
    offload_carries: bool = False
    # capability flags
    supports_long_context: bool = False  # may run the 500k decode shape
    optimizer: str = "adamw"             # adamw|adafactor (giants)
    source: str = ""                     # citation for the config

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.head_dim == 0:
            object.__setattr__(
                self, "head_dim",
                self.qk_nope_head_dim + self.qk_rope_head_dim
                if MLA in self.layer_pattern
                else self.d_model // max(self.n_heads, 1))
        if self.expert_d_ff == 0:
            object.__setattr__(self, "expert_d_ff", self.d_ff)
        if self.router_experts == 0:
            object.__setattr__(self, "router_experts", self.n_experts)
        if self.rnn_width == 0:
            object.__setattr__(self, "rnn_width", self.d_model)
        if self.n_layers % len(self.layer_pattern) != 0:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} not divisible by "
                f"layer_pattern of length {len(self.layer_pattern)}"
            )
        for k in self.layer_pattern:
            if k not in LAYER_KINDS:
                raise ValueError(f"unknown layer kind {k!r}")
        if self.arch_type == "moe" and (self.n_experts <= 0 or self.top_k <= 0):
            raise ValueError(f"{self.name}: moe arch needs n_experts/top_k")
        if self.is_moe and not self.moe_pattern:
            object.__setattr__(self, "moe_pattern",
                               tuple(k in (ATTN, SWA, MLA)
                                     for k in self.layer_pattern))
        if self.moe_pattern and len(self.moe_pattern) != len(self.layer_pattern):
            raise ValueError(f"{self.name}: moe_pattern length mismatch")
        self._check_mla_moe()

    def _check_mla_moe(self):
        if self.router not in ROUTERS:
            raise ValueError(f"{self.name}: router {self.router!r} not in "
                             f"{ROUTERS}")
        if self.expert_offset + self.n_experts > self.router_experts:
            raise ValueError(f"{self.name}: experts [{self.expert_offset}, "
                             f"{self.expert_offset + self.n_experts}) are "
                             f"not all among the router's "
                             f"{self.router_experts}")
        if self.is_moe and self.top_k > self.router_experts:
            raise ValueError(f"{self.name}: top_k above the router's width")
        if (self.first_dense_layers % len(self.layer_pattern)
                or self.first_dense_layers >= max(self.n_layers, 1)):
            raise ValueError(f"{self.name}: first_dense_layers must be whole "
                             f"layer groups and leave one to scan")
        if MLA in self.layer_pattern:
            if min(self.kv_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim) <= 0:
                raise ValueError(f"{self.name}: MLA needs kv_lora_rank and "
                                 f"the nope/rope/v head dims")
            if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
                raise ValueError(f"{self.name}: MLA head_dim is the q/k head "
                                 f"dim, nope + rope")
            if self.kv_cache_dtype == "int8":
                raise ValueError(f"{self.name}: int8 KV caches do not "
                                 f"support latent attention")

    # ------------------------------------------------------------------
    @property
    def n_groups(self) -> int:
        """Layer groups in all; the cache is stacked over these."""
        return self.n_layers // len(self.layer_pattern)

    @property
    def n_dense_groups(self) -> int:
        """Leading groups with dense FFNs, applied before the scan."""
        return self.first_dense_layers // len(self.layer_pattern)

    @property
    def n_scan_groups(self) -> int:
        return self.n_groups - self.n_dense_groups

    @property
    def rope_scaling_dict(self) -> dict:
        return dict(self.rope_scaling)

    @property
    def kv_row_elems(self) -> int:
        """KV values one token stores per layer: K and V of every KV head,
        or for MLA the latent row, c_kv followed by the rotary key."""
        if MLA in self.layer_pattern:
            return self.kv_lora_rank + self.qk_rope_head_dim
        return 2 * self.n_kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def attention_free(self) -> bool:
        return all(k in (RGLRU, RWKV) for k in self.layer_pattern)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    # -- parameter counting (used by placement / planner / roofline) ----
    def param_count(self) -> int:
        """Total parameters (embedding + layers + head)."""
        d = self.d_model
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        total = (emb + head
                 + self.n_dense_groups * self._group_params(False)
                 + self.n_scan_groups * self._group_params(True))
        if self.encoder_decoder:
            hd = self.head_dim
            enc_layer = (2 * d
                         + d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                         + self.n_heads * hd * d + self._ffn_params())
            cross = (d + d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                     + self.n_heads * hd * d)
            total += self.n_encoder_layers * enc_layer + self.n_layers * cross
        return total

    def _group_params(self, moe_allowed: bool) -> int:
        """Parameters of one layer group; ``moe_allowed`` False for the
        leading dense groups."""
        d, f = self.d_model, self.d_ff
        per_layer = 0
        for i, kind in enumerate(self.layer_pattern):
            moe_here = bool(moe_allowed and self.is_moe and self.moe_pattern
                            and self.moe_pattern[i])
            per_layer += 2 * d  # two norms
            if kind in (ATTN, SWA):
                hd = self.head_dim
                per_layer += d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                per_layer += self.n_heads * hd * d
                per_layer += self._ffn_params(moe_here)
            elif kind == MLA:
                per_layer += self._mla_params() + self._ffn_params(moe_here)
            elif kind == RGLRU:
                w = self.rnn_width
                per_layer += 2 * d * w + w * d      # in (x2 branches) + out
                per_layer += self.conv_width * w + w  # temporal conv
                per_layer += 3 * w                   # a_param + gate biases
                per_layer += 2 * w * w // 1          # gates (block-diag approx: dense here)
                per_layer += self._ffn_params(False)
            elif kind == RWKV:
                per_layer += 5 * d * d              # r,k,v,g + out
                per_layer += d * d                  # channel-mix receptance
                per_layer += 2 * d * f              # channel mix up/down
                per_layer += 140 * d                # mus, decay lora, u, ln_x
        return per_layer

    def _mla_params(self) -> int:
        d, h = self.d_model, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.q_lora_rank:
            q = d * self.q_lora_rank + self.q_lora_rank \
                + self.q_lora_rank * h * qk
        else:
            q = d * h * qk
        kv = (d * (self.kv_lora_rank + self.qk_rope_head_dim)
              + self.kv_lora_rank
              + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                         + self.v_head_dim))
        return q + kv + h * self.v_head_dim * d

    def _expert_params(self) -> int:
        gated = self.activation in ("swiglu", "geglu")
        return (3 if gated else 2) * self.d_model * self.expert_d_ff

    def _ffn_params(self, moe: bool | None = None) -> int:
        d, f = self.d_model, self.d_ff
        dense = 3 * d * f if self.activation in ("swiglu", "geglu") else 2 * d * f
        moe = self.is_moe if moe is None else moe
        if moe:
            router = d * self.router_experts
            if self.router == "sigmoid_bias":
                router += self.router_experts     # score correction bias
            return ((self.n_experts + self.n_shared_experts)
                    * self._expert_params() + router)
        return dense

    @property
    def n_moe_layers(self) -> int:
        if not self.is_moe:
            return 0
        return self.n_scan_groups * sum(bool(b) for b in self.moe_pattern)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: at most top_k of the experts
        held here, and the shared ones)."""
        if not self.is_moe:
            return self.param_count()
        inactive = (self.n_moe_layers
                    * max(self.n_experts - self.top_k, 0)
                    * self._expert_params())
        return self.param_count() - inactive

    def param_bytes(self, bytes_per_param: int = 2) -> int:
        return self.param_count() * bytes_per_param

    # ------------------------------------------------------------------
    def reduced(self, d_model: int = 256, n_layers: int = 0, n_experts: int = 4,
                vocab: int = 512) -> "ModelConfig":
        """Smoke-test variant of the same family: <=2 groups, tiny dims."""
        pat = self.layer_pattern
        if n_layers == 0:
            n_layers = len(pat) * min(2, self.n_groups)
        n_heads = max(2, min(4, self.n_heads))
        n_kv = 1 if self.n_kv_heads == 1 else max(1, min(2, self.n_kv_heads))
        while n_heads % n_kv:
            n_kv -= 1
        n_e = min(n_experts, self.n_experts) if self.is_moe else 0
        mla = {}
        if MLA in pat:
            mla = dict(q_lora_rank=min(self.q_lora_rank, d_model // 2),
                       kv_lora_rank=d_model // 4, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=24 if mla else d_model // n_heads,
            d_ff=d_model * 3,
            expert_d_ff=d_model * (3 if self.expert_d_ff == self.d_ff else 2),
            router_experts=(n_e if self.router_experts == self.n_experts
                            else min(self.router_experts, 2 * n_e)),
            vocab_size=vocab,
            n_experts=n_e,
            top_k=min(self.top_k, 2) if self.is_moe else 0,
            **mla,
            rnn_width=d_model,
            sliding_window=min(self.sliding_window, 64),
            n_encoder_layers=min(2, self.n_encoder_layers),
            encoder_len=32 if self.encoder_decoder else self.encoder_len,
            rwkv_head_size=32,
            dtype="float32",
            remat=False,
        )


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (see system brief).
@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    phase: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# The paper's own models (Mixtral target family + Mistral draft).
MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b", arch_type="moe", n_layers=32, d_model=4096,
    n_heads=32, n_kv_heads=8, d_ff=14336, vocab_size=32000,
    n_experts=8, top_k=2, rope_theta=1e6,
    source="arXiv:2401.04088",
)

MIXTRAL_8X22B = ModelConfig(
    name="mixtral-8x22b", arch_type="moe", n_layers=56, d_model=6144,
    n_heads=48, n_kv_heads=8, d_ff=16384, vocab_size=32768,
    n_experts=8, top_k=2, rope_theta=1e6,
    source="mistral.ai/news/mixtral-8x22b",
)

MISTRAL_7B = ModelConfig(
    name="mistral-7b", arch_type="dense", n_layers=32, d_model=4096,
    n_heads=32, n_kv_heads=8, d_ff=14336, vocab_size=32000,
    layer_pattern=(SWA,), sliding_window=4096, rope_theta=1e4,
    source="arXiv:2310.06825",
)
