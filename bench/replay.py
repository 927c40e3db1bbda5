"""The reference's side of a run: score the captured rows itself, follow
the program's first train steps with a plain AIPO + Adam in float32, and
read the numbers that are compared.

Nothing here imports the program.  The reward is recomputed from the
prompt text (``a+b=?`` or ``a-b=?``) and the first number of the
completion, as the published task defines it; the advantages are the
group-mean baseline over a prompt's samples.
"""
from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as bw

PAD, BOS, EOS = 0, 1, 2
_CHARS = "0123456789+-*/=?#<> ()abcdefghijklmnopqrstuvwxyz"
_ID_TO_CHAR = {i + 3: c for i, c in enumerate(_CHARS)}
ADAM_B1, ADAM_B2, ADAM_EPS, MAX_GRAD_NORM = 0.9, 0.95, 1e-8, 1.0


def text(ids) -> str:
    out = []
    for i in ids:
        i = int(i)
        if i == EOS:
            break
        if i in (PAD, BOS):
            continue
        out.append(_ID_TO_CHAR.get(i, "#"))
    return "".join(out)


def answer(prompt_ids) -> float:
    m = re.match(r"(\d+)([+\-*])(\d+)=\?", text(prompt_ids))
    a, op, b = int(m.group(1)), m.group(2), int(m.group(3))
    return float({"+": a + b, "-": a - b, "*": a * b}[op])


def reward(prompt_ids, completion_ids) -> float:
    m = re.search(r"-?\d+(?:\.\d+)?", text(completion_ids))
    return float(m is not None and abs(float(m.group(0))
                                       - answer(prompt_ids)) < 1e-6)


def advantages(tokens, prompt_len: int, n_per_prompt: int, mask):
    """Per-token group-mean advantages [B, T], zero off the action mask."""
    r = np.asarray([reward(t[:prompt_len], t[prompt_len:]) for t in tokens],
                   np.float32).reshape(-1, n_per_prompt)
    adv = (r - r.mean(axis=1, keepdims=True)).reshape(-1)
    return adv[:, None] * np.asarray(mask, np.float32)


def _blocks(n, size):
    return [(i, min(i + size, n)) for i in range(0, n, size)]


_JITTED = {}


def _jitted(kind, ref, spec, dtype, rho=None):
    """One compiled function per (kind, model, precision), so the blocks
    of a batch and the steps of a replay share it."""
    key = (kind, ref.__name__, json.dumps(spec, sort_keys=True), str(dtype),
           rho)
    if key not in _JITTED:
        if kind == "logp":
            fn = jax.jit(lambda p, t: ref.token_logp(p, spec, t, dtype))
        else:
            fn = jax.jit(jax.value_and_grad(
                lambda p, b: ref.aipo_loss_sum(p, spec, b, rho=rho,
                                               dtype=dtype), has_aux=True))
        _JITTED[key] = fn
    return _JITTED[key]


def batch_logp(ref, spec, params, tokens, *, dtype, rows_per_block):
    """log p of every token after the first, in blocks of rows."""
    f = _jitted("logp", ref, spec, dtype)
    return np.concatenate([np.asarray(f(params, jnp.asarray(tokens[a:b])))
                           for a, b in _blocks(len(tokens), rows_per_block)])


def loss_and_grads(ref, spec, params, batch, *, rho, dtype, rows_per_block):
    """The AIPO loss of the whole batch and its gradient, accumulated over
    blocks of rows and divided by the batch's action count at the end."""
    vg = _jitted("loss", ref, spec, dtype, rho)
    denom = max(float(np.sum(batch["mask"][:, 1:])), 1.0)
    total, lp_sum, grads = 0.0, 0.0, None
    for a, b in _blocks(len(batch["tokens"]), rows_per_block):
        part = {k: jnp.asarray(v[a:b]) for k, v in batch.items()}
        (val, lps), g = vg(params, part)
        total += float(val)
        lp_sum += float(lps)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    grads = jax.tree.map(lambda x: (x / denom).astype(jnp.float32), grads)
    return total / denom, lp_sum / denom, grads


@jax.jit
def _clip(grads):
    """The gradient clipped to the global norm, and that norm before."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, MAX_GRAD_NORM / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gn


def _adam(params, grads, m, v, step, lr):
    c1 = 1.0 - ADAM_B1 ** step
    c2 = 1.0 - ADAM_B2 ** step
    m = jax.tree.map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree.map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, v,
                     grads)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS)),
        params, m, v)
    return params, m, v


_adam_jit = jax.jit(_adam, donate_argnums=(2, 3))


def follow(ref, spec, seed, batches, *, lr, rho, n_per_prompt, prompt_len,
           dtype=jnp.float32, rows_per_block=2):
    """Rebuild the weights from the seed and follow the program's first
    ``len(batches)`` train steps.  Returns the log-prob of every token of
    the first batch (sampled under version 0 throughout: version 1 exists
    only once the first step has consumed that batch; later batches' rows
    decode under whatever version is current, which the program does not
    record per token), each step's loss and mean action log-prob, each
    step's global gradient norm before clipping and (clipped) gradient
    leaf norms, and the leaf norms of the change after the last step."""
    params, _ = bw.make(ref, spec, seed, prompt_len)
    w0 = params
    logp = batch_logp(ref, spec, params, batches[0]["tokens"], dtype=dtype,
                      rows_per_block=rows_per_block)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, mean_logp, grad_norms, global_norms = [], [], [], []
    for k, b in enumerate(batches):
        batch = {"tokens": b["tokens"], "behavior_logp": b["behavior_logp"],
                 "mask": b["mask"],
                 "advantages": advantages(b["tokens"], prompt_len,
                                          n_per_prompt, b["mask"])}
        loss, mlp, grads = loss_and_grads(ref, spec, params, batch, rho=rho,
                                          dtype=dtype,
                                          rows_per_block=rows_per_block)
        grads, gn = _clip(grads)
        global_norms.append(float(gn))
        losses.append(loss)
        mean_logp.append(mlp)
        grad_norms.append(np.asarray(bw.leaf_norms(grads), np.float64))
        params, m, v = _adam_jit(params, grads, m, v, k + 1, lr)
        del grads
    change = np.asarray(bw.leaf_norms(jax.tree.map(jnp.subtract, params, w0)),
                        np.float64)
    return {"logp": logp, "losses": losses, "mean_logp": mean_logp,
            "grad_norm": global_norms, "grad_norms": grad_norms,
            "change": change,
            "names": bw.leaf_names(params)}
