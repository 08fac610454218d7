"""Mixtral-8x7B target + Mistral-7B draft at published widths, cut in depth
to fit one TPU v5e (16 GB of HBM).

This is the pair ``chip_smoke.py`` and
``python -m repro.launch.serve --arch mixtral-8x7b-v5e-pair --no-reduced``
serve.

Sources: Mixtral of Experts, arXiv:2401.04088 (target); Mistral 7B,
arXiv:2310.06825 (draft).

Kept as published: d_model 4096, FFN 14336, 32 query and 8 KV heads of
128, vocab 32000, bfloat16; the target's 8 experts with top-2 routing and
RoPE theta 1e6; the draft's 4096-token sliding window and RoPE theta 1e4.

Changed keys:
  * target ``n_layers`` 32 -> 2;
  * draft ``n_layers`` 32 -> 4;
  * target ``moe_dropless`` False -> True: the published model routes
    every token to its top-2 experts with no capacity limit, which is
    what dropless dispatch computes (the base config's capacity factor
    of 2.0 may drop prefill tokens).

Deployment: each published model has 32 layers.  The layers cut here
would sit on further chips as pipeline stages; this chip holds one
stage of the target and the draft's first layers, whole (no expert,
head or vocabulary split).  Weights come to 8.6 GB: the target
3.16e9 parameters (6.33 GB in bf16), the draft 1.13e9 (2.27 GB).  The
rest of the chip holds the paged KV pools, prefill activations and the
fused verify+draft step's temporaries.
"""
import dataclasses

from repro.configs.base import MISTRAL_7B, MIXTRAL_8X7B

TARGET = dataclasses.replace(
    MIXTRAL_8X7B, name="mixtral-8x7b-2l", n_layers=2, moe_dropless=True,
    source="arXiv:2401.04088; n_layers 32->2 (one pipeline stage), "
           "dropless routing as published")

DRAFT = dataclasses.replace(
    MISTRAL_7B, name="mistral-7b-4l", n_layers=4,
    source="arXiv:2310.06825; n_layers 32->4")
