"""Host<->HBM weight streaming: the TPU-native realization of the paper's
PCIe offloading.

* Target weights at rest live in ``pinned_host`` memory (the analogue of
  the paper's CPU DRAM tier) and are copied into device memory per step
  with ``jax.device_put``.
* The KV cache may also live host-side, with decode attention computed
  under ``jax.experimental.compute_on('device_host')`` — the analogue of
  the paper's CPU-attention leg (§4.1.2).
* The draft model stays fully device-resident (the paper's "low-yield
  memory repurposing").

:class:`OffloadedModel` is not on the serving path yet: ``ServingEngine``
keeps the target resident, and :meth:`OffloadedModel.stream_layers`
copies the whole layer stack before each jitted call.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.compute_on import compute_on

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.transformer import (forward_decoder, init_cache,
                                      logits_from_hidden)
from repro.obs import NULL_OBS


def host_memory_kind(device=None) -> str:
    """The memory kind of the host offload tier: ``pinned_host``."""
    device = device or jax.devices()[0]
    kinds = {m.kind for m in device.addressable_memories()}
    if "pinned_host" not in kinds:
        raise ValueError(f"{device} exposes no pinned_host memory "
                         f"(has {sorted(kinds)})")
    return "pinned_host"


def _sharding(memory_kind: str, device=None):
    device = device or jax.devices()[0]
    return jax.sharding.SingleDeviceSharding(device, memory_kind=memory_kind)


def put_host(tree):
    """Move a pytree to pinned host memory (the offload tier)."""
    return jax.device_put(tree, _sharding("pinned_host"))


def put_device(tree):
    return jax.device_put(tree, _sharding("device"))


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def record_transfer(obs, tier: str, nbytes: float, seconds: float,
                    what: str = "transfer"):
    """Account one tier transfer in the metrics registry + trace.

    ``tier`` names the link direction ("h2d", "d2h"); bytes and seconds
    feed the ``transfer_bytes_total`` / ``transfer_seconds_total``
    counters, and a completed span lands on the matching trace track.
    """
    if not obs.enabled:
        return
    obs.metrics.counter(
        "transfer_bytes_total",
        "bytes moved across the offload link per tier").inc(
            float(nbytes), tier=tier)
    obs.metrics.counter(
        "transfer_seconds_total",
        "wall seconds spent on offload-link transfers per tier").inc(
            max(float(seconds), 0.0), tier=tier)
    if obs.tracer.enabled:
        t1 = time.perf_counter()
        obs.tracer.complete(tier, what, t1 - seconds, t1,
                            args={"bytes": float(nbytes)})


class OffloadedModel:
    """A model whose layer-group weights stream from host per step.

    ``params_host`` keeps ``layers`` in pinned host memory; embeddings +
    final norm (small, high reuse) stay device-resident, mirroring the
    placement plan's pinning priorities.
    """

    def __init__(self, cfg: ModelConfig, params: dict,
                 host_kv: bool = False, obs=None):
        self.cfg = cfg
        self.host_kv = host_kv
        self.obs = obs if obs is not None else NULL_OBS
        resident = {k: v for k, v in params.items() if k != "layers"}
        self.params_resident = put_device(resident)
        self.layers_host = put_host(params["layers"])
        record_transfer(self.obs, "d2h", tree_bytes(self.layers_host),
                        0.0, what="park_layers")

    # -- streamed forward ---------------------------------------------------

    def _assemble(self, layers_dev):
        p = dict(self.params_resident)
        p["layers"] = layers_dev
        return p

    @partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _decode_jit(self, layers_dev, cache, tokens):
        params = self._assemble(layers_dev)
        logits, cache, pendings = M.decode(params, self.cfg, cache, tokens)
        return logits, cache, pendings

    def stream_layers(self):
        """host->device copy of the layer stack (the per-step stream).

        Dispatch is asynchronous; compute on previously-streamed data
        overlaps with this copy, which is the paper's prefetch.  With a
        fencing tracer the transfer is blocked to completion (honest
        link seconds); otherwise only dispatch cost is visible.
        """
        if not self.obs.enabled:
            return put_device(self.layers_host)
        t0 = time.perf_counter()
        layers = put_device(self.layers_host)
        if self.obs.tracer.enabled and self.obs.tracer.fence_spans:
            jax.block_until_ready(layers)
        record_transfer(self.obs, "h2d", tree_bytes(self.layers_host),
                        time.perf_counter() - t0, what="stream_layers")
        return layers

    def decode(self, cache, tokens):
        layers_dev = self.stream_layers()
        return self._decode_jit(layers_dev, cache, tokens)

    def prefill(self, tokens, cache, encoder_frames=None):
        layers_dev = self.stream_layers()
        params = self._assemble(layers_dev)
        return jax.jit(M.prefill, static_argnums=(1,))(
            params, self.cfg, tokens, cache)

    def streamed_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(self.layers_host))


# ---------------------------------------------------------------------------
# host-offloaded decode attention (the CPU-attention analogue)


def host_attention_direct(q, k, v, mask, scale):
    """Decode attention computed in host memory space.

    Used by the single-chip offload engine when the KV cache is
    host-resident: the score/softmax/PV chain executes under
    ``compute_on('device_host')`` so only q (tiny) and the output cross
    the host link — the KV cache itself never moves, exactly like the
    paper's CPU attention.
    """
    from repro.models.attention import attention_direct
    with compute_on("device_host"):
        out = attention_direct(q, k, v, mask, scale)
    return out
