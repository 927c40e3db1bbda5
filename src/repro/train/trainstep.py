"""AIPO train-step factory: loss assembly, remat policy, Adam update.

batch layout (everything right-aligned to the full token sequence):
  tokens        [B, T] int32  -- prompt + sampled response
  behavior_logp [B, T] f32    -- mu's per-token logprob (0 on prompt)
  advantages    [B, T] f32    -- per-token advantage (0 on prompt)
  mask          [B, T] f32    -- 1 on *action* positions (response tokens)
  (+ optional frontend embeds: patch_embeds / frame_embeds)

Action position t is predicted by logits at t-1, so the loss aligns
``logits[:, :-1]`` with ``tokens[:, 1:]``.

Both the AIPO loss and the MTP auxiliary use ``aipo.token_logprobs``, which
routes through ``repro.kernels.dispatch``: log pi(y_t) is computed by
streaming vocab tiles (custom VJP included), so the grad of this step never
materializes a [B, T, V] fp32 log-softmax on top of the logits themselves.
Backend choice (Pallas compiled / interpreted / streamed jnp) follows the
``REPRO_KERNEL_MODE`` / ``REPRO_PALLAS_COMPILE`` env knobs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.aipo import aipo_loss, token_logprobs
from repro.models import forward_train
from repro.train.optimizer import AdamState, adam_init, adam_update


class TrainState(NamedTuple):
    params: Any
    opt: AdamState


def init_train_state(cfg, key, dtype=jnp.float32) -> TrainState:
    from repro.models import init_params
    params = init_params(cfg, key, dtype)
    return TrainState(params=params, opt=adam_init(params))


def make_loss_fn(cfg, *, rho=4.0, clip_mode="aipo", kl_coef=0.0,
                 mtp_weight=0.1, remat=False):
    def loss_fn(params, batch):
        fwd = forward_train
        if remat:
            fwd = jax.checkpoint(forward_train, static_argnums=(1,))
        logits, aux = fwd(params, cfg, batch)
        loss, metrics = aipo_loss(
            logits[:, :-1],
            batch["tokens"][:, 1:],
            batch["behavior_logp"][:, 1:],
            batch["advantages"][:, 1:],
            batch["mask"][:, 1:],
            rho=rho, clip_mode=clip_mode, kl_coef=kl_coef,
            ref_logp=(batch["ref_logp"][:, 1:]
                      if kl_coef and "ref_logp" in batch else None))
        moe_aux = aux.get("moe_aux", 0.0)
        loss = loss + moe_aux
        if "mtp_logits" in aux and mtp_weight:
            # multi-token-prediction auxiliary CE on t+2 targets
            mtp_logits = aux["mtp_logits"][:, :-2]
            tgt = batch["tokens"][:, 2:]
            m = batch["mask"][:, 2:]
            lp = token_logprobs(mtp_logits, tgt)
            mtp_loss = -jnp.sum(lp * m) / jnp.maximum(jnp.sum(m), 1.0)
            loss = loss + mtp_weight * mtp_loss
            metrics = dict(metrics, mtp_loss=mtp_loss)
        metrics = dict(metrics, moe_aux=moe_aux, total_loss=loss)
        for k in ("moe_pairs_held", "moe_experts_hit"):
            if k in aux:
                metrics[k] = aux[k]
        return loss, metrics
    return loss_fn


def make_train_step(cfg, *, lr=2e-7, rho=4.0, clip_mode="aipo", kl_coef=0.0,
                    max_grad_norm=1.0, weight_decay=0.0, mtp_weight=0.1,
                    remat=False, lr_fn=None, accum_steps: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    The paper's optimizer setting: Adam, fixed lr 2e-7 (Sec. 8.1).
    accum_steps > 1 splits the batch into microbatches and accumulates
    gradients with a lax.scan -- live activations shrink by the accumulation
    factor (the classic fix when global-batch activations exceed HBM)."""
    loss_fn = make_loss_fn(cfg, rho=rho, clip_mode=clip_mode, kl_coef=kl_coef,
                           mtp_weight=mtp_weight, remat=remat)

    def train_step(state: TrainState, batch) -> tuple:
        if accum_steps > 1:
            B = batch["tokens"].shape[0]
            assert B % accum_steps == 0, (B, accum_steps)
            micro = jax.tree.map(
                lambda a: a.reshape((accum_steps, B // accum_steps)
                                    + a.shape[1:]), batch)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (loss, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, mb)
                g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
                return (g_acc, l_acc + loss), metrics

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss), metrics = jax.lax.scan(acc, (zeros, 0.0), micro)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        step_lr = lr_fn(state.opt.step) if lr_fn is not None else lr
        params, opt, opt_metrics = adam_update(
            state.params, grads, state.opt, lr=step_lr,
            weight_decay=weight_decay, max_grad_norm=max_grad_norm)
        return TrainState(params, opt), {**metrics, **opt_metrics}

    return train_step
