"""Plain reference of the xLSTM[1:0] family (Beck et al.,
arXiv:2405.04517): pre-norm residual mLSTM blocks (matrix memory, in the
parallel form), no feed-forward, tied or untied embeddings.

As in the paper, the mLSTM up-projection is 2 x d_model, q and k come
from a causal depthwise convolution of it and v from the up-projection
itself, and the output is gated by a SiLU branch and projected down.
Departures from the paper, as the system under test makes them: q, k and
v are dense projections (the paper's are block-diagonal, blocks of 4),
the input and forget gates are projections of the convolved
up-projection, and the block has RMSNorm ahead of it and no group norm
or learnable skip.  sLSTM blocks are not written here: no configuration
of the benchmark has them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import Leaf, layer_groups, rmsnorm, stacked


def _mlstm_spec(d: int, h: int, width: int):
    up = 2 * d
    dh = up // h
    return {
        "norm": Leaf((d,), "ones"),
        "w_up": Leaf((d, up)),
        "w_gate": Leaf((d, up)),
        "conv": {"w": Leaf((width, up), scale=0.5), "b": Leaf((up,), "zeros")},
        "wq": Leaf((up, h, dh)),
        "wk": Leaf((up, h, dh)),
        "wv": Leaf((up, h, dh)),
        "wi": Leaf((up, h), scale=0.1),
        "bi": Leaf((h,), "const", -3.0),
        "wf": Leaf((up, h), scale=0.1),
        "bf": Leaf((h,), "const", 3.0),
        "w_down": Leaf((up, d)),
    }


def spec(cfg):
    d, h = cfg["d_model"], cfg["num_heads"]
    mixers = {"mlstm": _mlstm_spec(d, h, cfg["conv_width"])}
    groups = []
    for is_stacked, kinds, n in layer_groups(cfg["num_layers"],
                                             cfg["block_pattern"]):
        g = {}
        for i, kind in enumerate(kinds):
            block = {"mixer": mixers[kind]}
            g[f"pos{i}_{kind}"] = stacked(block, n) if is_stacked else block
        groups.append(g)
    out = {"embed": Leaf((cfg["vocab_size"], d)),
           "final_norm": Leaf((d,), "ones"),
           "groups": groups}
    if not cfg["tie_embeddings"]:
        out["unembed"] = Leaf((d, cfg["vocab_size"]))
    return out


def _causal_conv(p, x):
    """Depthwise causal convolution over the sequence: x (B, S, C)."""
    w = p["w"]
    width = w.shape[0]
    out = p["b"] + x * w[width - 1]
    for lag in range(1, width):
        past = jnp.pad(x, ((0, 0), (lag, 0), (0, 0)))[:, : x.shape[1]]
        out = out + past * w[width - 1 - lag]
    return out


def _mlstm(p, x, h):
    """x (B, S, d) -> (B, S, d), the parallel form of the mLSTM."""
    b, s, _ = x.shape
    xin = x @ p["w_up"]
    up = xin.shape[-1]
    dh = up // h
    c = jax.nn.silu(_causal_conv(p["conv"], xin))
    q = jnp.einsum("bsu,uhd->bhsd", c, p["wq"])
    k = jnp.einsum("bsu,uhd->bhsd", c, p["wk"])
    v = jnp.einsum("bsu,uhd->bhsd", xin, p["wv"])
    i_pre = jnp.einsum("bsu,uh->bhs", c, p["wi"]) + p["bi"][None, :, None]
    f_pre = jnp.einsum("bsu,uh->bhs", c, p["wf"]) + p["bf"][None, :, None]
    # log D[i, j] = sum_{j < t <= i} log f_t + log i_j, for j <= i
    cum = jnp.cumsum(jax.nn.log_sigmoid(f_pre.astype(jnp.float32)), -1)
    log_d = cum[..., :, None] - cum[..., None, :] + \
        i_pre.astype(jnp.float32)[..., None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    log_d = jnp.where(causal, log_d, -jnp.inf)
    m = jnp.max(log_d, -1, keepdims=True)
    weights = jnp.einsum("bhid,bhjd->bhij", q, k) * dh ** -0.5 * \
        jnp.exp(log_d - m)
    norm = jnp.maximum(jnp.abs(jnp.sum(weights, -1)), jnp.exp(-m[..., 0]))
    hid = jnp.einsum("bhij,bhjd->bhid", weights, v) / norm[..., None]
    hid = hid.transpose(0, 2, 1, 3).reshape(b, s, up).astype(x.dtype)
    return (hid * jax.nn.silu(x @ p["w_gate"])) @ p["w_down"]


MIXERS = {"mlstm": _mlstm}


def layers(params, cfg):
    """The layers in order: ``[(kind, params of that layer)]``."""
    out = []
    for (is_stacked, kinds, n), g in zip(
            layer_groups(cfg["num_layers"], cfg["block_pattern"]),
            params["groups"]):
        for r in range(n):
            for i, kind in enumerate(kinds):
                lp = g[f"pos{i}_{kind}"]
                if is_stacked:
                    lp = jax.tree.map(lambda a: a[r], lp)
                out.append((kind, lp))
    return out


def block(kind, lp, x, cfg):
    y = MIXERS[kind](lp["mixer"], rmsnorm(lp["mixer"]["norm"], x,
                                          cfg["norm_eps"]),
                     cfg["num_heads"])
    return x + y


def head(params, x, cfg):
    x = rmsnorm(params["final_norm"], x, cfg["norm_eps"])
    if cfg["tie_embeddings"]:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return x @ params["unembed"]
