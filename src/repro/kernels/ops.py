"""jit'd public wrappers for the Pallas kernels, and the one switch that
routes the model between compiled kernels and their references.

:func:`use_compiled_kernels` is asked at trace time, never at import, so
importing this module starts no JAX backend.  On a TPU it is True: the
wrappers dispatch the compiled kernels and the model's paged decode goes
through :func:`paged_decode_attention`.  Elsewhere it is False: wrappers
default to ``interpret=True`` (the kernel bodies run in Python, for
correctness checks) and the model takes the ``kernels/ref.py`` gather
path.  Tests steer both call sites at once with :func:`compiled_kernels`.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax

from repro.kernels import (decode_attention as _da, flash_attention as _fa,
                           moe_ffn as _mf, rglru_scan as _rg, wkv6 as _wk)

_FORCED = contextvars.ContextVar("compiled_kernels", default=None)


def use_compiled_kernels() -> bool:
    """True: compiled Pallas kernels; False: references / interpret mode.

    Defaults to whether the default backend is a TPU; a
    :func:`compiled_kernels` scope overrides it."""
    forced = _FORCED.get()
    if forced is not None:
        return forced
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def compiled_kernels(on: bool):
    """Force :func:`use_compiled_kernels` to ``on`` inside the scope (e.g.
    to compile the TPU kernels for a described, unattached chip)."""
    tok = _FORCED.set(on)
    try:
        yield
    finally:
        _FORCED.reset(tok)


def _wrap(fn, *static):
    """jit ``fn`` with ``static`` + ``interpret`` static, resolving
    ``interpret=None`` through :func:`use_compiled_kernels` *before* the
    jit cache lookup so a forced scope never reuses the other mode's
    trace."""
    jitted = jax.jit(fn, static_argnames=static + ("interpret",))

    @functools.wraps(fn)
    def call(*args, interpret=None, **kwargs):
        if interpret is None:
            interpret = not use_compiled_kernels()
        return jitted(*args, interpret=interpret, **kwargs)
    return call


flash_attention = _wrap(_fa.flash_attention, "scale", "causal", "window",
                        "block_q", "block_k")
decode_attention = _wrap(_da.decode_attention, "scale", "window", "block_k")
paged_decode_attention = _wrap(_da.paged_decode_attention, "scale")
paged_mla_attention = _wrap(_da.paged_mla_attention, "scale", "v_dim")
routed_expert_ffn = _wrap(_mf.routed_expert_ffn, "activation")
rglru_scan = _wrap(_rg.rglru_scan, "block_w")
wkv6 = _wrap(_wk.wkv6)
