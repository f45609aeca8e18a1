"""Model FLOPs and the chip's peaks.

A trained token costs 6 FLOPs per matmul weight it meets (forward 2,
backward 4), counting every matmul weight but the input embedding (a
lookup), plus the mLSTM's causal token mixing: 6 * 2 * (attended keys)
* head width per head, per layer, where a causal row attends on average
to half the context.  Recomputation (remat) is not counted.
The peaks are the published figures of each device kind; a kind that
is not in the table is an error.
"""
from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

from models.common import count, is_leaf

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _matmul_weights(cfg) -> int:
    """Every weight a token meets in a matmul: the normal-initialised
    parameters (not norms or biases) but the input embedding, plus the
    tied embedding once more where it is applied as the output head."""
    import jax

    from .reference import family
    spec = family(cfg["family"]).spec(cfg)
    flat = jax.tree_util.tree_flatten_with_path(spec, is_leaf=is_leaf)[0]
    n = sum(int(np.prod(s.shape)) for path, s in flat
            if s.init == "normal" and jax.tree_util.keystr(path) != "['embed']")
    if cfg["tie_embeddings"]:
        n += cfg["vocab_size"] * cfg["d_model"]
    return n


def _mixing_per_token(cfg, seq: int) -> float:
    """Forward FLOPs of the causal token mixing for one token, summed
    over layers: 2 matmuls (scores, weighted sum) of 2 FLOPs per
    multiply-add over the keys a causal row attends to on average."""
    total = 0.0
    kinds = cfg["block_pattern"]
    for i in range(cfg["num_layers"]):
        kind = kinds[i % len(kinds)]
        if kind != "mlstm":
            raise ValueError(f"no FLOPs count for {kind!r} layers")
        heads = cfg["num_heads"]
        width = 2 * cfg["d_model"] // heads
        total += 2 * 2 * heads * width * seq / 2
    return total


def per_token(cfg, seq: int) -> float:
    """Model FLOPs of training one token at context ``seq``."""
    return 6.0 * _matmul_weights(cfg) + 3.0 * _mixing_per_token(cfg, seq)


def param_count(cfg) -> int:
    from .reference import family
    return count(family(cfg["family"]).spec(cfg))
