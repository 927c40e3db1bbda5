"""FFN variants: SwiGLU / squared-ReLU / GELU MLPs and Mixture-of-Experts.

The expert layer is dropless and holds a share of the experts: a chip under
expert parallelism holds ``cfg.held_experts`` of the ``moe.n_experts``
(``experts_held`` computes any contiguous share; ``ep_shmap`` gives each
shard its own).  The router keeps its full width; every (token, held
expert) pair is computed, at any length and under any imbalance, and pairs
routed to absent experts add nothing -- the result is the held experts'
partial sum, which is what goes on to the next layer:

  route over all experts -> top-k (renormalized where the config says) ->
  pairs sorted by held expert (absent ones last) -> each held expert's
  rows laid out from a row-tile boundary -> gate, up and down as grouped
  products (``dispatch.grouped_matmul``) -> gathered back per (token, pick)
  and weighted (+ shared experts).

The layout is sized for the worst case, every token's picks on held
experts: ``tokens * min(top_k, held)`` rows plus a tile of padding per
expert, so nothing is ever dropped; the grouped product only computes the
tiles in use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.grouped_matmul import row_tile
from repro.models.common import act_fn, dense_init, split_keys


# ------------------------------------------------------------- dense MLP ---

def mlp_params(key, d_model, d_ff, act, dtype, bias=False):
    ks = split_keys(key, 3)
    if act == "silu_gated":
        p = {
            "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
            "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
            "w_down": dense_init(ks[2], (d_ff, d_model), dtype),
        }
    else:
        p = {
            "w_in": dense_init(ks[0], (d_model, d_ff), dtype),
            "w_down": dense_init(ks[1], (d_ff, d_model), dtype),
        }
    if bias:
        p["b_up"] = jnp.zeros((d_ff,), dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype)
    return p


def mlp_forward(p, x, act, bias=False):
    if "w_gate" in p:
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = x @ p["w_in"]
        if bias:
            h = h + p["b_up"]
        h = act_fn(h, act)
    y = h @ p["w_down"]
    if bias:
        y = y + p["b_down"]
    return y


# ------------------------------------------------------------------- MoE ---

#: what an expert layer tallies, summed over layers: the load-balance
#: term, the (token, held expert) pairs computed, held experts with a pair
TALLY = ("moe_aux", "moe_pairs_held", "moe_experts_hit")


def tally_dict(tally):
    return dict(zip(TALLY, tally))


def moe_params(key, cfg, dtype):
    """The router over all ``n_experts`` and the held experts' weights.
    Expert ``e`` is drawn from the ``e``-th of ``n_experts`` keys, so it
    is the same expert whichever share holds it."""
    m = cfg.moe
    D = cfg.d_model
    F = m.d_expert or cfg.d_ff
    n = cfg.held_experts
    ks = split_keys(key, 5)

    def expert(k):
        kg, ku, kd = split_keys(k, 3)
        return (dense_init(kg, (D, F), dtype), dense_init(ku, (D, F), dtype),
                dense_init(kd, (F, D), dtype))

    w_gate, w_up, w_down = jax.vmap(expert)(
        jax.random.split(ks[1], m.n_experts)[:n])
    p = {"w_router": dense_init(ks[0], (D, m.n_experts), jnp.float32),
         "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    if m.n_shared:
        p["shared"] = mlp_params(ks[4], D, m.n_shared * F, "silu_gated", dtype)
    return p


def _route(p, x, m):
    """Router probabilities + top-k weights.  x: [..., D] -> fp32.  The
    logits are a discrete choice's input, so they are computed in full
    float32 precision: the same top-k whatever the batch's shape."""
    logits = jnp.matmul(x.astype(jnp.float32), p["w_router"],
                        precision=jax.lax.Precision.HIGHEST)
    if m.router == "sigmoid":            # deepseek-v3 style
        probs = jax.nn.sigmoid(logits)
        vals, idx = jax.lax.top_k(probs, m.top_k)
        weights = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(probs, m.top_k)
        weights = vals / jnp.sum(vals, axis=-1, keepdims=True) \
            if m.norm_topk else vals
    return probs, weights, idx


def _balance_loss(probs, idx, m):
    """Switch-style load balance over all experts: E * sum_e f_e * P_e."""
    E = m.n_experts
    onehot_sum = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=-2)
    f_e = jnp.mean(onehot_sum.reshape(-1, E), axis=0) / m.top_k
    P_e = jnp.mean(probs.reshape(-1, E), axis=0)
    return E * jnp.sum(f_e * P_e) * m.aux_loss_coef


def experts_held(p, x, weights, idx, first, n_experts: int):
    """The held experts' part of the layer, dropless.

    x: [T, D]; weights, idx: [T, k] (expert ids over all ``n_experts``);
    ``p``'s expert weights are experts ``first .. first + G``.  Returns
    (y [T, D], pairs held, held experts hit)."""
    T, D = x.shape
    k = idx.shape[-1]
    G = p["w_gate"].shape[0]
    tm = row_tile(T * k / n_experts)
    M = -(-(T * min(k, G) + G * (tm - 1)) // tm) * tm
    local = idx.reshape(-1) - first
    group = jnp.where((local >= 0) & (local < G), local, G)     # G: absent
    sizes = jnp.bincount(group, length=G + 1).astype(jnp.int32)
    padded = (sizes + tm - 1) // tm * tm
    order = jnp.argsort(group, stable=True)
    g_sorted = group[order]
    # row of each sorted pair: its group's tile-aligned start plus its
    # rank in the group; absent pairs get row M (out of range)
    rank = jnp.arange(T * k) - (jnp.cumsum(sizes) - sizes)[g_sorted]
    row_sorted = jnp.where(g_sorted < G,
                           (jnp.cumsum(padded) - padded)[g_sorted] + rank, M)
    row_token = jnp.full((M,), T, jnp.int32).at[row_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    buf = x.at[row_token].get(mode="fill", fill_value=0)
    sizes = sizes[:G]
    h = dispatch.grouped_matmul(buf, p["w_gate"], sizes, tm=tm)
    u = dispatch.grouped_matmul(buf, p["w_up"], sizes, tm=tm)
    out = dispatch.grouped_matmul(jax.nn.silu(h) * u, p["w_down"], sizes,
                                  tm=tm)
    row = jnp.zeros((T * k,), jnp.int32).at[order].set(row_sorted)
    picked = out.at[row.reshape(T, k)].get(mode="fill", fill_value=0)
    y = jnp.sum(picked * weights[..., None].astype(out.dtype), axis=1)
    return y, jnp.sum(sizes), jnp.sum(sizes > 0)


def _experts_shmap(p, x, weights, idx, cfg, mesh):
    """moe_mode='ep_shmap': the held experts split over the mesh's 'model'
    axis.  Activations are replicated along 'model', so each shard has
    every token: it computes its own experts' part with
    ``experts_held`` and one psum over 'model' sums the parts."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.models.sharding import dp_axes
    n = cfg.held_experts
    mm = mesh.shape["model"]
    B, S, D = x.shape
    k = idx.shape[-1]
    dp = dp_axes(mesh)

    def local(xl, wl, il, wg, wu, wd):
        lo = jax.lax.axis_index("model") * (n // mm)
        b = xl.shape[0]
        y, pairs, hit = experts_held(
            {"w_gate": wg, "w_up": wu, "w_down": wd}, xl.reshape(-1, D),
            wl.reshape(-1, k), il.reshape(-1, k), lo, cfg.moe.n_experts)
        return (jax.lax.psum(y.reshape(b, S, D), "model"),
                jax.lax.psum(jnp.stack([pairs, hit]), ("model",) + dp))

    w = P("model", None, None)
    y, stats = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None),) * 3 + (w, w, w),
        out_specs=(P(dp, None, None), P()),
        check_rep=False,
    )(x, weights, idx, p["w_gate"], p["w_up"], p["w_down"])
    return y, stats[0], stats[1]


def moe_forward(p, x, cfg):
    """x: [B, S, D] -> (y, tally): the tally is ``TALLY``'s three numbers
    (float32), summed by the layer scans."""
    m = cfg.moe
    B, S, D = x.shape
    probs, weights, idx = _route(p, x, m)
    aux = _balance_loss(probs, idx, m) if m.aux_loss_coef else 0.0
    mesh = None
    if cfg.moe_mode == "ep_shmap":
        from repro.models.sharding import _ACT_MESH
        mesh = _ACT_MESH["mesh"]
    if mesh is not None and cfg.held_experts % mesh.shape["model"] == 0:
        y, pairs, hit = _experts_shmap(p, x, weights, idx, cfg, mesh)
    else:
        y, pairs, hit = experts_held(
            p, x.reshape(B * S, D), weights.reshape(B * S, -1),
            idx.reshape(B * S, -1), 0, m.n_experts)
        y = y.reshape(B, S, D)
    if m.n_shared:
        y = y + mlp_forward(p["shared"], x, "silu_gated")
    return y, jnp.stack([jnp.asarray(aux, jnp.float32),
                         pairs.astype(jnp.float32), hit.astype(jnp.float32)])
