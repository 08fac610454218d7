"""Pallas TPU kernels for the paper's compute hot-spots.

flash_attention  — prefill/train attention (the GPU-bound prefill phase)
decode_attention — skinny-q verification attention against long KV caches
moe_ffn          — routed expert FFN: reads only the experts a decode step chose
rglru_scan       — RecurrentGemma RG-LRU time scan
wkv6             — RWKV-6 WKV recurrence

``ops.py`` holds the jit'd wrappers (interpret=True on CPU); ``ref.py`` the
pure-jnp oracles each kernel is allclose-tested against.
"""
