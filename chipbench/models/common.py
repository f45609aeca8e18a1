"""What every reference shares: seeded parameter trees, the synthetic
token feed, RMSNorm, the next-token loss and AdamW.

The initialisation (normal weights of standard deviation
``scale / sqrt(fan_in)``, one PRNG key per leaf in tree order) and the
feed (a bigram permutation with 30% uniform noise) follow the system's
stated conventions, written out here, so that the reference rebuilds the
weights and batches a run trains on from the job's seed alone.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter: its shape and how it is initialised."""
    shape: Tuple[int, ...]
    init: str = "normal"          # normal | zeros | ones | const
    scale: float = 1.0


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def stacked(tree, n: int):
    """The same tree with a leading axis of ``n`` layers on every leaf."""
    return jax.tree.map(lambda s: Leaf((n,) + s.shape, s.init, s.scale),
                        tree, is_leaf=is_leaf)


def layer_groups(num_layers: int, pattern) -> List[Tuple[bool, tuple, int]]:
    """Whole repeats of ``pattern`` as one stacked group, the remainder
    unstacked: ``[(is_stacked, kinds, n)]``."""
    n_full, rem = divmod(num_layers, len(pattern))
    out = []
    if n_full:
        out.append((True, tuple(pattern), n_full))
    if rem:
        out.append((False, tuple(pattern[:rem]), 1))
    return out


def _materialize(leaf: Leaf, key, dtype):
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "const":
        return jnp.full(leaf.shape, leaf.scale, dtype)
    fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
    std = leaf.scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, leaf.shape, jnp.float32) * std).astype(dtype)


def init_tree(spec, key, dtype=jnp.float32):
    """Materialise ``spec`` from ``key``: one key per leaf, in tree order."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_materialize(s, k, dtype) for s, k in zip(leaves, keys)])


def count(spec) -> int:
    return int(sum(int(np.prod(s.shape))
                   for s in jax.tree.leaves(spec, is_leaf=is_leaf)))


class BigramFeed:
    """The seeded token stream a job trains on: each row starts at a
    uniform token, and each next token is ``perm[prev]`` or, with
    probability ``noise``, a uniform token.  Batch ``k`` is the ``k``-th
    draw of ``RandomState(seed + 1)``."""

    def __init__(self, vocab: int, seed: int, noise: float = 0.3):
        self.vocab = vocab
        self.seed = seed
        self.noise = noise
        self.perm = np.random.RandomState(seed).permutation(vocab)

    def batches(self, batch: int, seq: int, n: int) -> Iterator[np.ndarray]:
        rng = np.random.RandomState(self.seed + 1)
        v = self.vocab
        for _ in range(n):
            toks = np.empty((batch, seq + 1), np.int64)
            toks[:, 0] = rng.randint(0, v, batch)
            for t in range(1, seq + 1):
                nxt = self.perm[toks[:, t - 1]]
                flip = rng.rand(batch) < self.noise
                toks[:, t] = np.where(flip, rng.randint(0, v, batch), nxt)
            yield toks[:, :seq].astype(np.int32)


def rmsnorm(scale, x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def next_token_nll_sum(logits, tokens):
    """Summed next-token negative log-likelihood of ``tokens`` (B, S)
    under ``logits`` (B, S, V), over the B * (S - 1) predicted tokens."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(nll)


@dataclasses.dataclass(frozen=True)
class AdamW:
    """AdamW with global-norm clipping and warmup + cosine decay."""
    lr: float
    total_steps: int
    warmup_steps: int
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0

    @classmethod
    def from_recipe(cls, recipe: Dict[str, Any], lr: float,
                    total_steps: int) -> "AdamW":
        """``recipe`` is a configuration's ``optimizer`` entry; the warmup
        is ``min(warmup_cap, total_steps // warmup_div + 1)`` steps."""
        warm = min(int(recipe["warmup_cap"]),
                   total_steps // int(recipe["warmup_div"]) + 1)
        return cls(lr=lr, total_steps=total_steps, warmup_steps=warm,
                   b1=recipe["b1"], b2=recipe["b2"], eps=recipe["eps"],
                   weight_decay=recipe["weight_decay"],
                   grad_clip=recipe["grad_clip"])

    def lr_at(self, step: int) -> float:
        warm = min(1.0, (step + 1) / max(self.warmup_steps, 1))
        frac = min(max((step - self.warmup_steps)
                       / max(self.total_steps - self.warmup_steps, 1),
                       0.0), 1.0)
        return self.lr * warm * 0.5 * (1.0 + np.cos(np.pi * frac))

    def clip(self, grads):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, self.grad_clip / jnp.maximum(norm, 1e-9))
        return jax.tree.map(lambda g: g * scale, grads)

    def update(self, params, grads, mu, nu, step: int, lr: float):
        """One update from clipped ``grads``; ``step`` counts from 0."""
        t = step + 1
        bc1, bc2 = 1.0 - self.b1 ** t, 1.0 - self.b2 ** t

        def one(p, g, m, v):
            m = self.b1 * m + (1 - self.b1) * g
            v = self.b2 * v + (1 - self.b2) * g * g
            u = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
            return p - lr * (u + self.weight_decay * p), m, v

        out = jax.tree.map(one, params, grads, mu, nu)
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)
