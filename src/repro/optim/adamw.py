"""AdamW optimizer + LR schedules, pure-pytree (no optax dependency)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 1e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    schedule: str = "cosine"     # constant | cosine | linear
    warmup_steps: int = 100
    total_steps: int = 10000


# a job's own settings, which enter its step as float32 operands; the other
# fields shape the program, so jobs that differ only in these share one
HYPERPARAMS = ("lr", "weight_decay", "warmup_steps", "total_steps")


def split_hyperparams(cfg: AdamWConfig):
    """``(static, hyper)``: ``cfg`` with its hyperparameters taken out
    (None), which keys a shared step program, and the hyperparameters as
    float32 scalars, the operands that program takes in their place."""
    static = dataclasses.replace(cfg, **dict.fromkeys(HYPERPARAMS))
    return static, {k: np.float32(getattr(cfg, k)) for k in HYPERPARAMS}


def lr_at(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = jnp.minimum(1.0, (step + 1) / jnp.maximum(cfg.warmup_steps, 1))
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    if cfg.schedule == "cosine":
        decay = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    elif cfg.schedule == "linear":
        decay = 1.0 - frac
    else:
        decay = 1.0
    return cfg.lr * warm * decay


def init_opt_state(params):
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def adamw_update(cfg: AdamWConfig, params, grads, state):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"]
    gnorm = global_norm(grads)
    if cfg.grad_clip > 0:
        scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(cfg, step)
    t = (step + 1).astype(jnp.float32)
    bc1 = 1.0 - cfg.b1 ** t
    bc2 = 1.0 - cfg.b2 ** t

    def upd(p, g, mu, nu):
        g32 = g.astype(jnp.float32)
        mu_n = cfg.b1 * mu + (1 - cfg.b1) * g32
        nu_n = cfg.b2 * nu + (1 - cfg.b2) * g32 * g32
        update = (mu_n / bc1) / (jnp.sqrt(nu_n / bc2) + cfg.eps)
        p_n = p.astype(jnp.float32) - lr * (
            update + cfg.weight_decay * p.astype(jnp.float32))
        return p_n.astype(p.dtype), mu_n, nu_n

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    out = [upd(p, g, m, n) for p, g, m, n in
           zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_p = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_state = {
        "mu": jax.tree.unflatten(tdef, [o[1] for o in out]),
        "nu": jax.tree.unflatten(tdef, [o[2] for o in out]),
        "step": step + 1,
    }
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}
