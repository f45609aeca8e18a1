"""The plain reference's first training steps, at the timed sizes: the
seeded weights and batches of one job, next-token loss, gradients and
AdamW, in float32 at the highest matmul precision (or, for the control,
all of it in bfloat16: weights, activations, gradients and AdamW's
moments), over the whole batch at once, run once the program's state
is freed.  It imports nothing of the system under test."""
from __future__ import annotations

import dataclasses
import functools
import importlib
import json
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from models.common import (AdamW, BigramFeed, init_tree, is_leaf,
                           next_token_nll_sum)

PRECISIONS = {"float32": (jnp.float32, "highest"),
              "bfloat16": (jnp.bfloat16, "default")}


@dataclasses.dataclass
class Readings:
    """What the first steps of one job give: the tokens of each step's
    batch, the loss of each step, the norm of each leaf's clipped
    gradient at step 1, and the norm of each leaf's change after the
    steps, by leaf path."""
    tokens: List[np.ndarray]
    losses: List[float]
    grad: np.ndarray
    change: np.ndarray
    paths: List[str]


def family(name: str):
    return importlib.import_module("models." + name)


def leaf_paths(tree, leaf=None) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=leaf)
    return [jax.tree_util.keystr(p) for p, _ in flat]


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def change_norms(params, key, spec):
    """Per-leaf norm of ``params`` less the weights ``spec`` gets from
    ``key``: how far training has moved each leaf."""
    start = init_tree(spec, key, jnp.float32)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32) - s)))
                      for p, s in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(start))])


def faulty(tokens: np.ndarray, fault: Optional[str], vocab: int):
    """A batch as a fault would leave it: ``token`` alters one token of
    every row where the feed produces it."""
    if fault is None:
        return tokens
    if fault != "token":
        raise ValueError(f"unknown fault {fault!r}")
    t = tokens.copy()
    t[:, 1] = (t[:, 1] + 1) % vocab
    return t


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, precision: str):
    """The reference's jitted pieces for one configuration and precision,
    built once a process so that every job reuses them."""
    cfg = json.loads(cfg_json)
    fam = family(cfg["family"])
    spec = fam.spec(cfg)
    dtype, matmul = PRECISIONS[precision]
    opt = AdamW.from_recipe(cfg["optimizer"], 0.0, 1)   # lr passed per step

    def nll_sum(params, tokens):
        p = jax.tree.map(lambda a: a.astype(dtype), params)
        x = p["embed"][tokens]
        for kind, lp in fam.layers(p, cfg):
            x = jax.checkpoint(functools.partial(fam.block, kind, cfg=cfg))(
                lp, x)
        return next_token_nll_sum(fam.head(p, x, cfg), tokens)

    with jax.default_matmul_precision(matmul):
        return dict(
            spec=spec, dtype=dtype,
            init=jax.jit(functools.partial(init_tree, spec, dtype=dtype)),
            grad_sum=jax.jit(jax.value_and_grad(nll_sum)),
            finish=jax.jit(lambda g, n: opt.clip(jax.tree.map(
                lambda x: x / n, g)), donate_argnums=(0,)),
            update=jax.jit(opt.update, donate_argnums=(0, 2, 3)),
            norms=jax.jit(leaf_norms),
            moved=jax.jit(functools.partial(change_norms, spec=spec)),
            matmul=matmul)


def first_steps(cfg: dict, seed: int, *, batch: int, seq: int, lr: float,
                total_steps: int, steps: int = 3,
                precision: str = "float32",
                fault: Optional[str] = None) -> Readings:
    """The reference's first ``steps`` steps of the job seeded ``seed``."""
    f = _programs(json.dumps(cfg, sort_keys=True), precision)
    opt = AdamW.from_recipe(cfg["optimizer"], lr, total_steps)
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision(f["matmul"]):
        params = f["init"](key)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        feed = BigramFeed(cfg["vocab_size"], seed)
        losses, grad, seen = [], None, []
        for t, tokens in enumerate(feed.batches(batch, seq, steps)):
            tokens = faulty(tokens, fault, cfg["vocab_size"])
            seen.append(tokens)
            total, g = f["grad_sum"](params, jnp.asarray(tokens))
            n = batch * (seq - 1)
            losses.append(float(total) / n)
            g = f["finish"](g, float(n))
            if t == 0:
                grad = np.asarray(f["norms"](g))
            params, mu, nu = f["update"](params, g, mu, nu, t,
                                         jnp.asarray(opt.lr_at(t),
                                                     f["dtype"]))
            del g
        change = np.asarray(f["moved"](params, key))
    return Readings(seen, losses, grad, change,
                    leaf_paths(f["spec"], is_leaf))
