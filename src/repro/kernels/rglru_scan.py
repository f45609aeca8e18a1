"""Pallas TPU kernel for the RG-LRU linear recurrence
h_t = a_t * h_{t-1} + b_t  (Griffin / RecurrentGemma temporal mixing).

Grid: (batch, num_r_blocks, num_s_blocks) with the sequence dimension
minor-most: each (b, ir) program walks its sequence blocks in order,
carrying h in VMEM scratch.  Inside a block a ``fori_loop`` walks
(8, block_r) tiles, the unit Mosaic loads and stores at a dynamic
offset, and steps through each tile's 8 time steps, indexed statically,
on (1, block_r) vectors — elementwise VPU work; there is no MXU
component, so the kernel's job is purely to keep the carry resident in
VMEM and stream a/b through HBM exactly once (the associative-scan
reference does log(S) passes over HBM instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8


def _rglru_kernel(a_ref, b_ref, y_ref, h_ref, *, block_s: int,
                  block_r: int):
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, block_r), 0)

    def tile(j, h):
        span = pl.ds(pl.multiple_of(j * _SUBLANES, _SUBLANES), _SUBLANES)
        a = a_ref[0, span, :].astype(jnp.float32)
        b = b_ref[0, span, :].astype(jnp.float32)
        y = jnp.zeros_like(a)
        for t in range(_SUBLANES):
            h = a[t:t + 1] * h + b[t:t + 1]            # (1, block_r)
            y = jnp.where(rows == t, h, y)
        y_ref[0, span, :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s // _SUBLANES, tile,
                                   h_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_r", "interpret"))
def rglru_scan(a, b, *, block_s: int = 256, block_r: int = 128,
               interpret: bool = False):
    """a, b: (B, S, R) -> h: (B, S, R) with h_t = a_t h_{t-1} + b_t."""
    bsz, s, r = a.shape
    block_s = min(block_s, s)
    block_r = min(block_r, r)
    assert s % block_s == 0 and r % block_r == 0
    assert block_s % _SUBLANES == 0
    grid = (bsz, r // block_r, s // block_s)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, block_s=block_s,
                          block_r=block_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_r),
                         lambda b_, ir, isb: (b_, isb, ir)),
            pl.BlockSpec((1, block_s, block_r),
                         lambda b_, ir, isb: (b_, isb, ir)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_r),
                               lambda b_, ir, isb: (b_, isb, ir)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, r), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        interpret=interpret,
    )(a, b)
