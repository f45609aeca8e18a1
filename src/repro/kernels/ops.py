"""Jit'd public wrappers for the Pallas TPU kernels.

``kernel_opts(cfg)`` builds an ``opts`` dict for the model layer
(``forward(..., opts=...)``) that would route the attention / RG-LRU /
mLSTM hot-spots through the Pallas kernels.  It is not on the training
path yet: ``BuiltJob`` builds its step without ``opts``, and the kernels
have no backward pass, so every trained or profiled step runs the
pure-jnp blockwise paths.  Off TPU, ``interpret=True`` executes the
kernel bodies (the tests do this).
"""
from __future__ import annotations

import jax

from .flash_attention import flash_attention
from .mlstm_chunk import mlstm_chunk
from .rglru_scan import rglru_scan

__all__ = ["flash_attention", "rglru_scan", "mlstm_chunk", "kernel_opts"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_opts(cfg=None, *, force: bool = False, interpret: bool = False):
    """opts dict wiring the kernels into the model forward pass."""
    if not (on_tpu() or force):
        return {}
    ip = interpret or not on_tpu()
    return {
        "attn_fn": lambda q, k, v, w: flash_attention(
            q, k, v, window=w, interpret=ip),
        "rglru_scan": lambda a, b: rglru_scan(a, b, interpret=ip),
        "mlstm_fn": lambda q, k, v, i_, f_: mlstm_chunk(
            q, k, v, i_, f_, interpret=ip),
    }
