"""Plain reference of the dense decoder family: GQA with rotary positions,
LayerNorm or RMSNorm, a GELU or SwiGLU feed-forward, an untied output
head.  Straight ``jax.numpy``, no kernels, no cache, no batching tricks;
it imports nothing of the program.

Parameters are drawn as the program's own initializer draws them (same
tree, same key splits, same scales), so a test can hold the two equal.
The model follows StarCoder2 / DeepSeek-LLM as published, with the
program's two departures mirrored so that every leaf can be compared:
no bias on the attention output projection and none on the norms.

``dtype`` is the compute type.  In float32 every matrix product runs at
``Precision.HIGHEST``; in bfloat16 (the control) parameters, activations
and products are all bfloat16 at the chip's default precision.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

PAD, EOS = 0, 2


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


# ------------------------------------------------------------------ init ---

def _dense(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * (1.0 /
                                                         np.sqrt(shape[0]))


def _layer(key, spec):
    D, H, K, hd, F = (spec["d_model"], spec["n_heads"], spec["n_kv_heads"],
                      spec["head_dim"], spec["d_ff"])
    ks = list(jax.random.split(key, 5))
    ka = list(jax.random.split(ks[0], 4))
    attn = {"wq": _dense(ka[0], (D, H * hd)), "wk": _dense(ka[1], (D, K * hd)),
            "wv": _dense(ka[2], (D, K * hd)), "wo": _dense(ka[3], (H * hd, D))}
    if spec["bias"]:
        attn.update(bq=jnp.zeros((H * hd,)), bk=jnp.zeros((K * hd,)),
                    bv=jnp.zeros((K * hd,)))
    km = list(jax.random.split(ks[1], 3))
    if spec["act"] == "silu_gated":
        mlp = {"w_gate": _dense(km[0], (D, F)), "w_up": _dense(km[1], (D, F)),
               "w_down": _dense(km[2], (F, D))}
    else:
        mlp = {"w_in": _dense(km[0], (D, F)), "w_down": _dense(km[1], (F, D))}
    if spec["bias"]:
        mlp.update(b_up=jnp.zeros((F,)), b_down=jnp.zeros((D,)))
    return {"ln1": jnp.ones((D,)), "attn": attn, "ln2": jnp.ones((D,)),
            "mlp": mlp}


def init(spec, key):
    """float32 parameters from a raw ``PRNGKey``."""
    ks = list(jax.random.split(key, 8))
    V, D = spec["vocab"], spec["d_model"]
    params = {"embed": _dense(ks[0], (V, D)), "final_norm": jnp.ones((D,))}
    assert not spec["tie_embeddings"]
    params["lm_head"] = _dense(ks[1], (D, V))
    lk = jnp.stack(list(jax.random.split(ks[2], spec["n_layers"])))
    params["layers"] = jax.vmap(lambda k: _layer(k, spec))(lk)
    return params


# --------------------------------------------------------------- forward ---

def _norm(x, w, kind):
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * w
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w


def _rope(x, theta):
    """x: [B, S, heads, hd]; rotate-half rotary embedding at 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, spec, dtype):
    B, S, _ = x.shape
    H, K, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    pr = _prec(dtype)
    q = jnp.matmul(x, p["wq"], precision=pr)
    k = jnp.matmul(x, p["wk"], precision=pr)
    v = jnp.matmul(x, p["wv"], precision=pr)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(B, S, H, hd), spec["rope_theta"])
    k = _rope(k.reshape(B, S, K, hd), spec["rope_theta"])
    v = v.reshape(B, S, K, hd)
    q = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k, precision=pr) * (hd ** -0.5)
    row = jnp.arange(S)[:, None]
    col = jnp.arange(S)[None, :]
    mask = col <= row
    if spec.get("window") and S > spec["window"]:
        mask &= col > row - spec["window"]
    s = jnp.where(mask, s, jnp.asarray(-1e30 if dtype == jnp.float32
                                       else -3e38, s.dtype))
    probs = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs, v, precision=pr)
    return jnp.matmul(o.reshape(B, S, H * hd), p["wo"], precision=pr)


def _mlp(p, x, spec, dtype):
    pr = _prec(dtype)
    if "w_gate" in p:
        h = jax.nn.silu(jnp.matmul(x, p["w_gate"], precision=pr)) \
            * jnp.matmul(x, p["w_up"], precision=pr)
    else:
        h = jnp.matmul(x, p["w_in"], precision=pr)
        if "b_up" in p:
            h = h + p["b_up"]
        h = jax.nn.gelu(h, approximate=True)
    y = jnp.matmul(h, p["w_down"], precision=pr)
    if "b_down" in p:
        y = y + p["b_down"]
    return y


def hidden(params, spec, tokens, dtype=jnp.float32):
    """Final-normed hidden states [B, S, D]."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed"][tokens]

    def layer(x, p):
        x = x + _attention(p["attn"], _norm(x, p["ln1"], spec["norm"]), spec,
                           dtype)
        x = x + _mlp(p["mlp"], _norm(x, p["ln2"], spec["norm"]), spec, dtype)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _norm(x, params["final_norm"], spec["norm"])


def logits(params, spec, tokens, dtype=jnp.float32):
    h = hidden(params, spec, tokens, dtype)
    return jnp.matmul(h, params["lm_head"].astype(dtype),
                      precision=_prec(dtype))


def token_logp(params, spec, tokens, dtype=jnp.float32):
    """log p(tokens[:, t] | tokens[:, :t]) for t >= 1: [B, S - 1]."""
    lg = logits(params, spec, tokens, dtype)[:, :-1]
    lp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0] \
        .astype(jnp.float32)


def aipo_loss_sum(params, spec, batch, *, rho, dtype=jnp.float32):
    """AIPO surrogate summed over the rows given (not yet divided by the
    batch's action count): ``-sum min(pi/mu, rho) * A * log pi``, the
    clipped weight held constant.  The sum of ``log pi`` over the action
    positions rides along as the auxiliary output."""
    lp = token_logp(params, spec, batch["tokens"], dtype)
    blp = batch["behavior_logp"][:, 1:]
    adv = batch["advantages"][:, 1:]
    m = batch["mask"][:, 1:]
    w = jax.lax.stop_gradient(jnp.minimum(jnp.exp(lp - blp), rho))
    return jnp.sum(-w * adv * lp * m), jnp.sum(lp * m)
