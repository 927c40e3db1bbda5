"""Sharding rules: logical roles -> PartitionSpecs on the production mesh.

Axis conventions (paper Sec. 4.3 / Table 3):
  * trainer: FSDP over the ``data`` axis + tensor parallel over ``model``
    (paper: FSDP/3D trainer); across pods we run plain data parallelism
    (batch sharded over ``pod``, params replicated) -- the paper-faithful
    baseline.  The hillclimb explores FSDP-over-pod etc.
  * generator/serve: tensor parallel over ``model`` only, params replicated
    over ``data``/``pod`` (paper: small-mp inference engine).

Every rule degrades gracefully: an axis is only sharded if its size divides
by the mesh axis (e.g. seamless's vocab 256206 % 16 != 0 -> replicated).
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]


def _fit(mesh: Mesh, shape, spec: Tuple) -> P:
    """Drop spec axes whose mesh size does not divide the dim."""
    fitted = []
    for dim, ax in zip(shape, spec):
        if ax is not None and dim % _axis_size(mesh, ax) == 0:
            fitted.append(ax)
        else:
            fitted.append(None)
    return P(*fitted)


def dp_axes(mesh: Mesh):
    """Data-parallel axes: ('pod','data') on multi-pod, ('data',) else."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


# ----------------------------------------------------- activation anchors --
# XLA's sharding propagation can drop the batch sharding of scan carries
# (observed: per-device dots over the FULL global token count).  Model code
# anchors activations with constrain_batch(); the launcher installs the mesh
# here before tracing.  Without a context (single-device tests) it's a no-op.

import contextlib

_ACT_MESH = {"mesh": None, "seq_parallel": False}


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, seq_parallel: bool = False):
    prev = (_ACT_MESH["mesh"], _ACT_MESH["seq_parallel"])
    _ACT_MESH["mesh"] = mesh
    _ACT_MESH["seq_parallel"] = seq_parallel
    try:
        yield
    finally:
        _ACT_MESH["mesh"], _ACT_MESH["seq_parallel"] = prev


def constrain_batch(x):
    """Anchor activation x: [B, ...] -- B sharded over the dp axes.

    With seq_parallel, residual-stream activations [B, S, D] additionally
    shard S over 'model' (Megatron-style sequence parallelism): XLA places
    all-gather/reduce-scatter at the TP boundaries and the elementwise/norm
    work between blocks runs on S/TP tokens per device."""
    mesh = _ACT_MESH["mesh"]
    if mesh is None or not hasattr(x, "ndim") or x.ndim < 1:
        return x
    seq_ax = "model" if (_ACT_MESH["seq_parallel"] and x.ndim == 3) else None
    spec = (dp_axes(mesh), seq_ax) + (None,) * (x.ndim - 2) if x.ndim >= 2 \
        else (dp_axes(mesh),)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _fit(mesh, x.shape, spec)))


def constrain_attn(q, k, v):
    """Anchor attention tensors q:[B,S,H,hd], k/v:[B,S,K,hd].

    Without this, XLA inherits the flat [D, K*hd] weight sharding and splits
    the *hd* contraction dim when K doesn't divide the model axis -- every
    score tensor then needs a partial-sum all-reduce (observed: 61 GB/layer
    at 32k prefill).  Rule:
      * K %% model == 0: shard heads over 'model' (aligned GQA TP);
      * else: replicate heads over 'model' (data-parallel attention) --
        correct, and far cheaper than partial-score all-reduces; the
        model axis still carries FFN/vocab TP."""
    mesh = _ACT_MESH["mesh"]
    if mesh is None:
        return q, k, v
    m = mesh.shape["model"]
    dp = dp_axes(mesh)
    K = k.shape[2]
    head_ax = "model" if K % m == 0 else None

    def c(t):
        spec = (dp, None, head_ax, None)
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, _fit(mesh, t.shape, spec)))
    return c(q), c(k), c(v)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


# role rules: (regex on path, spec builder given ndim-without-stack-dim)
# fsdp = the FSDP shard axis ('data'), tp = 'model'.
_RULES = [
    (r"embed$",            lambda f, t: (t, None)),          # [V, D]
    (r"lm_head$",          lambda f, t: (None, t)),          # [D, V]
    (r"wq$|wk$|wv$|w_gate$|w_up$|w_in$|wq_b$|wk_b$|wv_b$|w_qkv$|w_if$|w_x$",
                           lambda f, t: (f, t)),             # [D, F]
    (r"wo$|w_down$|w_out$",
                           lambda f, t: (t, f)),             # [F, D]
    (r"wq_a$|wkv_a$",      lambda f, t: (f, None)),
    (r"w_router$",         lambda f, t: (None, None)),
    (r"proj$",             lambda f, t: (f, t)),             # mtp proj
    (r"conv_w$",           lambda f, t: (None, t)),
    (r"r_h$",              lambda f, t: (None, None, None)),
    (r"A_log$|D_skip$|dt_bias$",
                           lambda f, t: (t,)),
]

_MOE_RULES = [
    # stacked expert weights [E, D, F] / [E, F, D]: experts over model (EP)
    (r"moe/w_gate$|moe/w_up$", lambda f, t: (t, f, None)),
    (r"moe/w_down$",           lambda f, t: (t, None, f)),
]


def param_spec(path: str, leaf, mesh: Mesh, *, mode: str,
               stacked: bool) -> P:
    """mode: 'train' (FSDP+TP) or 'serve' (TP only)."""
    fsdp = "data" if mode == "train" else None
    tp = "model"
    shape = leaf.shape
    core_shape = shape[1:] if stacked else shape
    spec: Optional[Tuple] = None
    for pat, builder in _MOE_RULES:
        if re.search(pat, path):
            spec = builder(fsdp, tp)
            break
    if spec is None:
        for pat, builder in _RULES:
            if re.search(pat, path):
                spec = builder(fsdp, tp)
                break
    if spec is None or len(spec) != len(core_shape):
        spec = (None,) * len(core_shape)
    if stacked:
        spec = (None,) + tuple(spec)
    return _fit(mesh, shape, spec)


def _is_stacked(path: str) -> bool:
    return bool(re.search(
        r"(^|/)(layers|moe_layers|dense_layers|mamba_layers|enc_layers|"
        r"dec_layers)/", path))


def params_shardings(params, mesh: Mesh, mode: str = "train"):
    def spec_of(path, leaf):
        ps = _path_str(path)
        return NamedSharding(
            mesh, param_spec(ps, leaf, mesh, mode=mode,
                             stacked=_is_stacked(ps)))
    return jax.tree_util.tree_map_with_path(spec_of, params)


def batch_shardings(batch, mesh: Mesh):
    """Shard the leading (batch) dim over the data-parallel axes."""
    dp = dp_axes(mesh)

    def spec_of(leaf):
        shape = leaf.shape
        spec = (dp,) + (None,) * (len(shape) - 1)
        return NamedSharding(mesh, _fit(mesh, shape, spec))
    return jax.tree.map(spec_of, batch)


def cache_shardings(cache, mesh: Mesh):
    """KV/state caches: batch dim over dp; if batch unshardable (B=1 long
    context), shard the cache sequence dim over 'data' instead."""
    dp = dp_axes(mesh)
    dp_size = _axis_size(mesh, tuple(dp))

    def spec_of(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        if leaf.ndim == 0 or "pos" in ps:
            return NamedSharding(mesh, P())
        # stacked [L, B, Sc, ...] for kv/ckv; states [L, B, ...]
        if re.search(r"/(k|v|ckv|krope)$", ps) and leaf.ndim >= 3:
            if shape[1] % dp_size == 0:
                spec = (None, dp, None) + (None,) * (leaf.ndim - 3)
            elif shape[2] % _axis_size(mesh, "data") == 0:
                spec = (None, None, "data") + (None,) * (leaf.ndim - 3)
            else:
                spec = (None,) * leaf.ndim
            return NamedSharding(mesh, _fit(mesh, shape, spec))
        if leaf.ndim >= 2:
            # recurrent states [L, B, ...] or [B, ...]
            bdim = 1 if leaf.ndim >= 3 else 0
            spec = [None] * leaf.ndim
            if shape[bdim] % dp_size == 0:
                spec[bdim] = dp
            return NamedSharding(mesh, _fit(mesh, shape, tuple(spec)))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(spec_of, cache)


def state_shardings(state, mesh: Mesh):
    """TrainState: params + adam moments share the param rules; step scalar
    replicated."""
    params_sh = params_shardings(state.params, mesh, mode="train")
    m_sh = params_shardings(state.opt.m, mesh, mode="train")
    v_sh = params_shardings(state.opt.v, mesh, mode="train")
    from repro.train.optimizer import AdamState
    from repro.train.trainstep import TrainState
    return TrainState(
        params=params_sh,
        opt=AdamState(step=NamedSharding(mesh, P()), m=m_sh, v=v_sh))
