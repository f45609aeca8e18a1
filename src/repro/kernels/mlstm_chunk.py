"""Pallas TPU kernel for chunkwise-parallel mLSTM (xLSTM matrix memory).

Grid: (batch, heads, num_chunks), chunks minor-most so each (b, h)
program walks its sequence chunks in order carrying the recurrent state
(C: d x d matrix memory, n: d normalizer, m: scalar stabilizer) in VMEM
scratch.  Within a chunk the math is the quadratic intra-chunk form —
two (L, d) x (d, L/d) matmuls on the MXU — plus rank-L state update,
exactly mirroring ``repro.models.blockwise.mlstm_chunked`` (the oracle).

TPU adaptation: the gates stream in as lane-major (1, L) rows; the
stabilizer m is a lane-replicated (1, 128) tile; the cumulative sum of
log-sigmoid forget gates is a lane reduction under a tril mask from 2-D
iota (no warp-level primitives, no 1-D vectors).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, o_ref,
                  c_ref, n_ref, m_ref, *, chunk: int, head_dim: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)

    L, d = chunk, head_dim
    scale = d ** -0.5
    q = q_ref[0, 0].astype(jnp.float32) * scale        # (L, d)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    # gates arrive lane-major, one (1, L) row per chunk; every per-step
    # vector is kept 2-D, and the column forms are taken through the
    # diagonal of an (L, L) mask (Mosaic has no 1-D vectors)
    it_row = i_ref[0, 0].astype(jnp.float32)           # (1, L)
    lf_row = jax.nn.log_sigmoid(f_ref[0, 0].astype(jnp.float32))
    m_prev = m_ref[:, :1]                              # (1, 1)
    C = c_ref[...]                                     # (d, d)
    n = n_ref[...]                                     # (1, d)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = row >= col
    diag = row == col
    # cum[t] = sum_{s <= t} lf[s], as a column and as a row
    cum_col = jnp.sum(jnp.where(causal, lf_row, 0.0), axis=1,
                      keepdims=True)                   # (L, 1)
    cum_row = jnp.sum(jnp.where(diag, cum_col, 0.0), axis=0,
                      keepdims=True)                   # (1, L)
    it_col = jnp.sum(jnp.where(diag, it_row, 0.0), axis=1, keepdims=True)
    g = jnp.sum(lf_row, axis=1, keepdims=True)         # (1, 1)
    logd = jnp.where(causal, cum_col - cum_row + it_row, -jnp.inf)
    m_intra = jnp.max(logd, axis=1, keepdims=True)     # (L, 1)
    m_inter = cum_col + m_prev
    m_i = jnp.maximum(jnp.maximum(m_intra, m_inter), -1e30)
    dmat = jnp.exp(logd - m_i)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    cmat = scores * dmat                               # (L, L)
    inter_w = jnp.exp(m_inter - m_i)                   # (L, 1)
    h_inter = jax.lax.dot_general(q, C, (((1,), (0,)), ((), ()))) \
        * inter_w                                      # (L, d)
    n_inter = jnp.sum(q * n, axis=1, keepdims=True) * inter_w
    h_intra = jax.lax.dot_general(cmat, v, (((1,), (0,)), ((), ())))
    n_total = jnp.sum(cmat, axis=1, keepdims=True) + n_inter
    denom = jnp.maximum(jnp.abs(n_total), jnp.exp(-m_i))
    o_ref[0, 0, :, :] = ((h_intra + h_inter) / denom).astype(o_ref.dtype)

    # ---- state update
    m_next = jnp.maximum(g + m_prev, jnp.max(it_row + g - cum_row, axis=1,
                                             keepdims=True))
    # lane-broadcast before the exp: Mosaic cannot broadcast a (1, 1)
    # value across sublanes and lanes at once
    decay = jnp.exp(jnp.broadcast_to(g + m_prev - m_next, (1, d)))
    w_in = jnp.exp(it_col + g - cum_col - m_next)      # (L, 1)
    kw = k * w_in                                      # (L, d)
    c_ref[...] = decay * C + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())))               # (d, d)
    n_ref[...] = decay * n + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk(q, k, v, i_pre, f_pre, *, chunk: int = 128,
                interpret: bool = False):
    """q,k,v: (B,S,H,D); i_pre,f_pre: (B,S,H) -> (B,S,H,D).

    Matches ``repro.models.blockwise.mlstm_chunked`` /
    ``repro.models.recurrent.mlstm_parallel_ref``."""
    b, s, h, d = q.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    qt = q.transpose(0, 2, 1, 3)                       # (B,H,S,D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ip = i_pre.transpose(0, 2, 1)[:, :, None]          # (B,H,1,S)
    fp = f_pre.transpose(0, 2, 1)[:, :, None]
    grid = (b, h, nc)
    seq_spec = pl.BlockSpec((1, 1, chunk, d),
                            lambda b_, h_, ic: (b_, h_, ic, 0))
    gate_spec = pl.BlockSpec((1, 1, 1, chunk),
                             lambda b_, h_, ic: (b_, h_, 0, ic))
    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk=chunk, head_dim=d),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, gate_spec, gate_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),           # C
            pltpu.VMEM((1, d), jnp.float32),           # n
            pltpu.VMEM((1, _LANES), jnp.float32),      # m (lane-replicated)
        ],
        interpret=interpret,
    )(qt, kt, vt, ip, fp)
    return out.transpose(0, 2, 1, 3)
