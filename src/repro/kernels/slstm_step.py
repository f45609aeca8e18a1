"""Pallas TPU kernel for the sLSTM recurrence (xLSTM scalar memory).

The sLSTM's per-step recurrent matmuls (h @ R_z/i/f/o, each (D, D) per
head) make it latency-bound when expressed as a 4096-iteration XLA while
loop over HBM-resident state (see EXPERIMENTS.md §Perf pair 1).  This
kernel keeps the state (c, n, m, h) AND the four recurrent matrices
resident in VMEM across the whole sequence:

Grid: (batch, heads, num_s_blocks) — s minor-most, so each (b, h)
program walks its sequence blocks in order; gate pre-activations stream
in (4, block_s, D) tiles, read 8 time steps at a time; per step a
(1, D) x (D, D) matmul per gate runs on the MXU from VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8


def _slstm_kernel(gates_ref, rz_ref, ri_ref, rf_ref, ro_ref, y_ref,
                  c_ref, n_ref, m_ref, h_ref, *, block_s: int, d: int):
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        h_ref[...] = jnp.zeros_like(h_ref)

    rz = rz_ref[0].astype(jnp.float32)       # (D, D), R[h]: out, in
    ri = ri_ref[0].astype(jnp.float32)
    rf = rf_ref[0].astype(jnp.float32)
    ro = ro_ref[0].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, d), 0)

    def tile(j, state):
        # Mosaic loads and stores whole (8, D) tiles at dynamic offsets;
        # the 8 time steps inside a tile index the loaded values
        # statically
        span = pl.ds(pl.multiple_of(j * _SUBLANES, _SUBLANES), _SUBLANES)
        gz, gi, gf, go = (gates_ref[0, 0, e, span, :].astype(jnp.float32)
                          for e in range(4))          # each (8, D)
        y = jnp.zeros((_SUBLANES, d), jnp.float32)
        c, n, m, h = state                   # each (1, D) f32
        for t in range(_SUBLANES):
            # recurrent contribution: pres_e = gx_e + sum_d h_d R[e, d]
            hz = jax.lax.dot_general(h, rz, (((1,), (1,)), ((), ())))
            hi = jax.lax.dot_general(h, ri, (((1,), (1,)), ((), ())))
            hf = jax.lax.dot_general(h, rf, (((1,), (1,)), ((), ())))
            ho = jax.lax.dot_general(h, ro, (((1,), (1,)), ((), ())))
            z = jnp.tanh(gz[t:t + 1] + hz)
            i_pre = gi[t:t + 1] + hi
            lf = jax.nn.log_sigmoid(gf[t:t + 1] + hf)
            o = jax.nn.sigmoid(go[t:t + 1] + ho)
            m_new = jnp.maximum(lf + m, i_pre)
            fg = jnp.exp(lf + m - m_new)
            ig = jnp.exp(i_pre - m_new)
            c = fg * c + ig * z
            n = jnp.maximum(fg * n + ig, 1e-6)
            m = m_new
            h = o * c / n
            y = jnp.where(rows == t, h, y)
        y_ref[0, 0, span, :] = y.astype(y_ref.dtype)
        return c, n, m, h

    state = (c_ref[...], n_ref[...], m_ref[...], h_ref[...])
    c, n, m, h = jax.lax.fori_loop(0, block_s // _SUBLANES, tile, state)
    c_ref[...], n_ref[...], m_ref[...], h_ref[...] = c, n, m, h


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def slstm_step_scan(gates, rz, ri, rf, ro, *, block_s: int = 128,
                    interpret: bool = False):
    """gates: (B, S, H, D, 4) pre-activations (incl. biases);
    rz/ri/rf/ro: (H, D, D) recurrent weights (R[h, out, in]).
    Returns h sequence (B, S, H, D).  Matches the naive scan in
    ``repro.models.recurrent.slstm_block``."""
    b, s, h, d, _ = gates.shape
    block_s = min(block_s, s)
    assert s % block_s == 0 and block_s % _SUBLANES == 0
    gt = gates.transpose(0, 2, 4, 1, 3)      # (B, H, 4, S, D)
    grid = (b, h, s // block_s)
    out = pl.pallas_call(
        functools.partial(_slstm_kernel, block_s=block_s, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 4, block_s, d),
                         lambda b_, h_, isb: (b_, h_, 0, isb, 0)),
            pl.BlockSpec((1, d, d), lambda b_, h_, isb: (h_, 0, 0)),
            pl.BlockSpec((1, d, d), lambda b_, h_, isb: (h_, 0, 0)),
            pl.BlockSpec((1, d, d), lambda b_, h_, isb: (h_, 0, 0)),
            pl.BlockSpec((1, d, d), lambda b_, h_, isb: (h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_s, d),
                               lambda b_, h_, isb: (b_, h_, isb, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), gates.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),  # c
            pltpu.VMEM((1, d), jnp.float32),  # n
            pltpu.VMEM((1, d), jnp.float32),  # m
            pltpu.VMEM((1, d), jnp.float32),  # h
        ],
        interpret=interpret,
    )(gt, rz, ri, rf, ro)
    return out.transpose(0, 2, 1, 3)
