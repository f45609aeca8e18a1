"""Training and serving step functions.

``train_step`` — causal-LM loss (next-token CE; audio archs use provided
codec labels; VLM masks the patch prefix), AdamW update, MoE aux loss.
``serve_step`` — single-token decode against a KV/recurrent-state cache
(this is what the decode_32k / long_500k dry-run shapes lower).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.transformer import decode_step, forward
from ..optim.adamw import AdamWConfig, adamw_update


def _ce_from_logits(cfg: ModelConfig, logits, batch):
    """Mean next-token cross-entropy.  Audio archs use provided codec
    labels (aligned); others shift tokens; VLM skips the patch prefix."""
    if cfg.frontend == "audio":
        targets = batch["labels"]
        pred = logits
    else:
        tokens = batch["tokens"]
        n_prefix = logits.shape[1] - tokens.shape[1]  # VLM patch prefix
        pred = logits[:, n_prefix:][:, :-1]
        targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    return loss, {"loss": loss,
                  "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def lm_loss(params, cfg: ModelConfig, batch, *, opts=None, remat=False):
    """Mean next-token cross-entropy (+ MoE aux).  Returns (loss, metrics)."""
    logits, aux = forward(params, cfg, batch, opts=opts, remat=remat)
    loss, metrics = _ce_from_logits(cfg, logits, batch)
    metrics["aux_loss"] = aux
    return loss + aux, metrics


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, *,
                    opts: Optional[dict] = None, remat: bool = False,
                    microbatches: int = 1, loss_fn=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    The step takes an optional fourth argument ``hyper``: the optimizer
    fields named in ``adamw.HYPERPARAMS`` as traced operands, in place of
    ``opt_cfg``'s constants (``parallelism.build`` shares one program so).

    microbatches > 1 accumulates gradients over batch slices (gradient
    accumulation; the GPipe technique instead passes its own pipelined
    ``loss_fn`` and keeps microbatches=1 here).
    """

    if loss_fn is None:
        def loss_fn(params, batch):
            return lm_loss(params, cfg, batch, opts=opts, remat=remat)

    def train_step(params, opt_state, batch, hyper=None):
        if microbatches == 1:
            grads, metrics = jax.grad(
                lambda p: loss_fn(p, batch), has_aux=True)(params)
        else:
            def split(x):
                b = x.shape[0]
                mb = b // microbatches
                return x.reshape(microbatches, mb, *x.shape[1:])
            mbatch = jax.tree.map(split, batch)

            def body(carry, mb):
                acc = carry
                g, m = jax.grad(
                    lambda p: loss_fn(p, mb), has_aux=True)(params)
                acc = jax.tree.map(jnp.add, acc, g)
                return acc, m
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, ms = jax.lax.scan(body, zero, mbatch)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda x: x.mean(), ms)
        ocfg = opt_cfg if hyper is None else \
            dataclasses.replace(opt_cfg, **hyper)
        new_params, new_opt, opt_metrics = adamw_update(
            ocfg, params, grads, opt_state)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    return train_step


def make_serve_step(cfg: ModelConfig, *, opts: Optional[dict] = None,
                    greedy: bool = True):
    """Returns serve_step(params, tokens (B,1), state) ->
    (next_tokens (B,1), logits, new_state)."""

    def serve_step(params, tokens, state):
        logits, new_state = decode_step(params, cfg, tokens, state, opts=opts)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return nxt, logits, new_state

    return serve_step
