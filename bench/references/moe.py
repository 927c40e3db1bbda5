"""Plain reference of the sparse-expert decoder family as Mellum2 publishes
it: GQA with plain RoPE on sliding-window layers and YaRN on full layers,
RMSNorm, an expert layer of SwiGLU experts under a softmax top-k router,
an untied output head.  Straight ``jax.numpy``, no kernels, no cache, no
sorting of tokens; it imports nothing of the program.

It holds the experts ``[expert_offset, expert_offset + experts_held)`` of
``n_experts``, as one chip does under expert parallelism: the router
keeps its full width, and each held expert is computed densely on every
token, times its (renormalized) top-k weight or 0 where the token did not
pick it.  Pairs on absent experts add nothing; that partial sum is what
goes on to the next layer.

What it reads of the configuration (``spec``): the sizes the benchmark
checks against the program (``n_layers``, ``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff`` -- the expert width --, ``vocab``,
``rope_theta``, ``window``), the published keys only the release names
(``layer_types``, ``rope_parameters``, ``num_experts_per_tok``,
``norm_topk_prob``, ``rms_norm_eps``), and the share (``n_experts``, the
router's width; ``experts_held``; ``expert_offset``).

Conventions, written from the published model, not from the program:

* the sliding-window mask lets query ``r`` see keys ``c`` with
  ``r - window < c <= r`` (the last ``window`` positions, itself
  included), as Hugging Face's sliding-window mask does;
* YaRN (arXiv:2309.00071, as ``_compute_yarn_parameters`` with its
  default ``truncate``): pair ``i``'s frequency ``theta^(-2i/d)`` is kept
  below the correction range and divided by ``factor`` above it, with a
  linear ramp between; the range runs from ``floor`` of the dim at which
  ``beta_fast`` rotations fit ``original_max_position_embeddings`` to
  ``ceil`` of the one for ``beta_slow``; cos and sin are scaled by
  ``attention_factor``.

Parameters are drawn as the program's initializer draws them (same tree,
same key splits, same scales; expert ``e`` from the ``e``-th of
``n_experts`` keys), so a test can hold the two equal.  One departure,
for the benchmark's weights only: where the configuration's ``weights``
group gives ``embed_std``, the embedding is that draw rescaled to that
standard deviation.  The program draws it at ``1 / sqrt(vocab)``, so a
token's own part of the residual stream is a few hundredths of what the
random attention layers add, which is much the same at every position;
every router then sees much the same input and the sampled text repeats
a few tokens.  A trained model's routing follows the token
(arXiv:2402.01739, section 4); at unit variance the token's part leads.  ``dtype`` is the
compute type: in float32 every product runs at ``Precision.HIGHEST``; in
bfloat16 (the control) parameters, activations and products are all
bfloat16 at the chip's default precision.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

PAD, EOS = 0, 2


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _mm(a, b, dtype):
    return jnp.matmul(a, b, precision=_prec(dtype))


def held(spec):
    """(first, count) of the experts held."""
    n = spec.get("experts_held") or spec["n_experts"]
    return spec.get("expert_offset", 0), n


# ------------------------------------------------------------------ init ---

def _dense(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * (1.0 /
                                                         np.sqrt(shape[0]))


def _layer(key, spec):
    D, H, K, hd, F = (spec["d_model"], spec["n_heads"], spec["n_kv_heads"],
                      spec["head_dim"], spec["d_ff"])
    E = spec["n_experts"]
    first, n = held(spec)
    ks = list(jax.random.split(key, 5))
    ka = list(jax.random.split(ks[0], 4))
    attn = {"wq": _dense(ka[0], (D, H * hd)), "wk": _dense(ka[1], (D, K * hd)),
            "wv": _dense(ka[2], (D, K * hd)), "wo": _dense(ka[3], (H * hd, D))}
    km = list(jax.random.split(ks[1], 5))

    def expert(k):
        kg, ku, kd = list(jax.random.split(k, 3))
        return _dense(kg, (D, F)), _dense(ku, (D, F)), _dense(kd, (F, D))

    wg, wu, wd = jax.vmap(expert)(jax.random.split(km[1], E)[first:first + n])
    moe = {"w_router": _dense(km[0], (D, E)), "w_gate": wg, "w_up": wu,
           "w_down": wd}
    return {"ln1": jnp.ones((D,)), "attn": attn, "ln2": jnp.ones((D,)),
            "moe": moe}


def init(spec, key):
    """float32 parameters from a raw ``PRNGKey``: the program's draws,
    the embedding at ``weights.embed_std`` where the configuration gives
    one."""
    ks = list(jax.random.split(key, 8))
    V, D = spec["vocab"], spec["d_model"]
    embed = _dense(ks[0], (V, D))
    std = spec.get("weights", {}).get("embed_std")
    if std is not None:
        embed = embed * (std * np.sqrt(V))
    params = {"embed": embed, "final_norm": jnp.ones((D,)),
              "lm_head": _dense(ks[1], (D, V))}
    lk = jnp.stack(list(jax.random.split(ks[3], spec["n_layers"])))
    params["moe_layers"] = jax.vmap(lambda k: _layer(k, spec))(lk)
    return params


# --------------------------------------------------------------- forward ---

def _norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def yarn_inv_freq(hd, theta, rp):
    """YaRN's inverse frequencies and attention factor from a published
    ``rope_parameters`` group."""
    base = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)

    def dim_for(rotations):
        return hd * np.log(rp["original_max_position_embeddings"]
                           / (rotations * 2 * np.pi)) / (2 * np.log(theta))
    lo = max(np.floor(dim_for(rp["beta_fast"])), 0)
    hi = min(np.ceil(dim_for(rp["beta_slow"])), hd - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(hd // 2) - lo) / (hi - lo), 0.0, 1.0)
    inv = base / rp["factor"] * ramp + base * (1.0 - ramp)
    return inv, rp["attention_factor"]


def _rope(x, inv, scale):
    """x: [B, S, heads, hd]; rotate-half rotary embedding at 0..S-1."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    cos = (jnp.cos(ang) * scale)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * scale)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_kind(spec, i):
    """(full attention?, inverse frequencies, cos/sin scale) of layer i."""
    hd, theta = spec["head_dim"], spec["rope_theta"]
    full = spec["layer_types"][i] == "full_attention"
    rp = spec["rope_parameters"]["full_attention" if full
                                 else "sliding_attention"]
    assert rp["rope_theta"] == theta, (rp, theta)
    if rp["rope_type"] == "yarn":
        inv, scale = yarn_inv_freq(hd, theta, rp)
    else:
        assert rp["rope_type"] == "default", rp
        inv, scale = theta ** (-np.arange(0, hd, 2) / hd), 1.0
    return full, inv, scale


def _attention(p, x, spec, dtype, full, inv, scale):
    B, S, _ = x.shape
    H, K, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    pr = _prec(dtype)
    q = _rope(_mm(x, p["wq"], dtype).reshape(B, S, H, hd), inv, scale)
    k = _rope(_mm(x, p["wk"], dtype).reshape(B, S, K, hd), inv, scale)
    v = _mm(x, p["wv"], dtype).reshape(B, S, K, hd)
    q = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k, precision=pr) * (hd ** -0.5)
    row = jnp.arange(S)[:, None]
    col = jnp.arange(S)[None, :]
    mask = col <= row
    if not full:
        mask &= col > row - spec["window"]
    s = jnp.where(mask, s, jnp.asarray(-1e30 if dtype == jnp.float32
                                       else -3e38, s.dtype))
    probs = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs, v, precision=pr)
    return _mm(o.reshape(B, S, H * hd), p["wo"], dtype)


def route(p, x, spec, dtype=jnp.float32):
    """Top-k weight of each held expert for each token, 0 where the token
    did not pick it: [..., held]."""
    first, n = held(spec)
    logits = _mm(x, p["w_router"], dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, spec["num_experts_per_tok"])
    if spec["norm_topk_prob"]:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    pick = idx[..., None] == (first + jnp.arange(n))
    return jnp.sum(jnp.where(pick, vals[..., None], 0.0), axis=-2).astype(
        dtype)


def experts(p, x, spec, dtype=jnp.float32):
    """The held experts' part of the layer: every held expert on every
    token, weighted by ``route``."""
    gate = route(p, x, spec, dtype)
    pr = _prec(dtype)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"],
                               precision=pr)) \
        * jnp.einsum("bsd,edf->bsef", x, p["w_up"], precision=pr)
    y = jnp.einsum("bsef,efd->bsed", h, p["w_down"], precision=pr)
    return jnp.einsum("bsed,bse->bsd", y, gate, precision=pr)


def hidden(params, spec, tokens, dtype=jnp.float32):
    """Final-normed hidden states [B, S, D]."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    eps = spec["rms_norm_eps"]
    x = params["embed"][tokens]
    for i in range(spec["n_layers"]):
        p = jax.tree.map(lambda a: a[i], params["moe_layers"])
        full, inv, scale = _layer_kind(spec, i)
        x = x + _attention(p["attn"], _norm(x, p["ln1"], eps), spec, dtype,
                           full, inv, scale)
        x = x + experts(p["moe"], _norm(x, p["ln2"], eps), spec, dtype)
    return _norm(x, params["final_norm"], eps)


def logits(params, spec, tokens, dtype=jnp.float32):
    h = hidden(params, spec, tokens, dtype)
    return _mm(h, params["lm_head"].astype(dtype), dtype)


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
