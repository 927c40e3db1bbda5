"""Backbone: per-family model assembly with scan-over-layers.

Public API (used by trainer, rollout engine, dry-run):

    init_params(cfg, key, dtype)             -> params
    forward_train(params, cfg, batch)        -> (logits [B,S,V], aux)
    init_cache(cfg, batch, cache_len, dtype) -> cache
    prefill(params, cfg, batch)              -> (last_logits [B,V], cache)
    decode_step(params, cfg, cache, tok, pos)-> (logits [B,V], cache)

Layer stacks are scanned (stacked params, one traced body per homogeneous
segment) so the 61..126-layer full configs lower with small HLO.  Hybrid
(zamba2) interleaves a *shared* attention block every k Mamba layers as an
unrolled outer loop over scanned Mamba segments; xLSTM (24 small layers,
two block kinds) is unrolled.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import ffn as ffnmod
from repro.models import ssm as ssmmod
from repro.models.common import (dense_init, norm, sinusoidal_positions,
                                 split_keys, text_mrope_positions)
from repro.models.sharding import constrain_batch

Params = Dict[str, Any]


# ------------------------------------------------------------------ init ---

def _attn_layer_params(key, cfg, dtype, *, moe: bool, cross: bool = False):
    ks = split_keys(key, 5)
    p = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if cfg.attn_kind == "mla":
        p["attn"] = attn.mla_params(ks[0], cfg, dtype)
    else:
        p["attn"] = attn.gqa_params(ks[0], cfg, dtype)
    p["ln2"] = jnp.ones((cfg.d_model,), dtype)
    if moe:
        p["moe"] = ffnmod.moe_params(ks[1], cfg, dtype)
    elif cfg.d_ff:
        p["mlp"] = ffnmod.mlp_params(ks[1], cfg.d_model, cfg.d_ff, cfg.act,
                                     dtype, bias=cfg.bias)
    if cross:
        p["ln_cross"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = attn.gqa_params(ks[2], cfg, dtype)
    return p


def _mamba_layer_params(key, cfg, dtype):
    return {"ln1": jnp.ones((cfg.d_model,), dtype),
            "mamba": ssmmod.mamba2_params(key, cfg, dtype)}


def _stack(fn, keys):
    return jax.vmap(fn)(jnp.stack(keys))


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    ks = split_keys(key, 8)
    params: Params = {
        "embed": dense_init(ks[0], (cfg.vocab, cfg.d_model), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab), dtype)

    if cfg.family in ("dense", "vlm"):
        layer_keys = split_keys(ks[2], cfg.n_layers)
        params["layers"] = _stack(
            lambda k: _attn_layer_params(k, cfg, dtype, moe=False), layer_keys)
    elif cfg.family == "moe":
        fkd = cfg.moe.first_k_dense
        if fkd:
            dk = split_keys(ks[2], fkd)
            params["dense_layers"] = _stack(
                lambda k: _attn_layer_params(k, cfg, dtype, moe=False), dk)
        mk = split_keys(ks[3], cfg.n_layers - fkd)
        params["moe_layers"] = _stack(
            lambda k: _attn_layer_params(k, cfg, dtype, moe=True), mk)
        if cfg.mtp:
            mks = split_keys(ks[4], 3)
            params["mtp"] = {
                "proj": dense_init(mks[0], (2 * cfg.d_model, cfg.d_model),
                                   dtype),
                "block": _attn_layer_params(mks[1], cfg, dtype, moe=False),
                "norm": jnp.ones((cfg.d_model,), dtype),
            }
    elif cfg.family == "hybrid":
        layer_keys = split_keys(ks[2], cfg.n_layers)
        params["mamba_layers"] = _stack(
            lambda k: _mamba_layer_params(k, cfg, dtype), layer_keys)
        params["shared_attn"] = _attn_layer_params(ks[3], cfg, dtype,
                                                   moe=False)
    elif cfg.family == "ssm":      # xlstm
        layer_keys = split_keys(ks[2], cfg.n_layers)
        layers = []
        for i, k in enumerate(layer_keys):
            cell = (ssmmod.slstm_params(k, cfg, dtype)
                    if i in cfg.xlstm.slstm_layers
                    else ssmmod.mlstm_params(k, cfg, dtype))
            layers.append({"ln": jnp.ones((cfg.d_model,), dtype),
                           "cell": cell})
        params["xlstm_layers"] = layers
    elif cfg.family == "audio":    # enc-dec
        ek = split_keys(ks[2], cfg.n_enc_layers)
        params["enc_layers"] = _stack(
            lambda k: _attn_layer_params(k, cfg, dtype, moe=False), ek)
        dk = split_keys(ks[3], cfg.n_layers)
        params["dec_layers"] = _stack(
            lambda k: _attn_layer_params(k, cfg, dtype, moe=False, cross=True),
            dk)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
    else:
        raise ValueError(cfg.family)
    return params


# ------------------------------------------------------- layer forwards ----

def _is_global_layer(cfg, i):
    """window_pattern: every Nth layer is global (full attention)."""
    if not cfg.window:
        return True
    if cfg.window_pattern:
        return (i + 1) % cfg.window_pattern == 0
    return False


def layer_yarn(cfg, i):
    """Layer ``i``'s YaRN table: the config's on its full-attention
    layers, None (plain RoPE) elsewhere."""
    return cfg.yarn if cfg.yarn is not None and _is_global_layer(cfg, i) \
        else None


def _layer_windows(cfg, n_layers, offset=0):
    return jnp.array(
        [0 if _is_global_layer(cfg, offset + i) else cfg.window
         for i in range(n_layers)], dtype=jnp.int32)


def _attn_block(p, x, cfg, *, window, mrope_pos=None, q_offset=0,
                yarn=None):
    h = norm(x, p["ln1"], cfg.norm)
    if cfg.attn_kind == "mla":
        y, kv = attn.mla_forward(p["attn"], h, cfg, q_offset=q_offset)
    else:
        y, kv = attn.gqa_forward(p["attn"], h, cfg, window=window,
                                 mrope_pos=mrope_pos, q_offset=q_offset,
                                 yarn=yarn)
    return x + y, kv


def _ffn_block(p, x, cfg):
    """(x + the layer's FFN, its tally): an expert layer's
    ``ffn.moe_forward`` tally, 0.0 for a dense one."""
    h = norm(x, p["ln2"], cfg.norm)
    if "moe" in p:
        y, aux = ffnmod.moe_forward(p["moe"], h, cfg)
    else:
        y = ffnmod.mlp_forward(p["mlp"], h, cfg.act, bias=cfg.bias)
        aux = 0.0
    return x + y, aux


def zero_tally(stacked):
    """What a layer stack's tallies sum from: a vector for expert layers
    (``ffn.TALLY``), the scalar 0.0 for dense ones."""
    return jnp.zeros((len(ffnmod.TALLY),)) if "moe" in stacked else 0.0


def scan_tallied(body, x, xs, cfg, stacked):
    """``_scan`` a layer stack with ``body(h, inputs) -> (h, tally, ys)``:
    returns (x, summed tally, ys).  A dense stack's tallies (0.0) are
    dropped inside the body, so its program is the plain scan over h."""
    if "moe" not in stacked:
        def plain(h, inputs):
            h, _, ys = body(h, inputs)
            return h, ys
        x, ys = _scan(plain, x, xs, cfg)
        return x, 0.0, ys

    def tallied(carry, inputs):
        h, t = carry
        h, a, ys = body(h, inputs)
        return (h, t + a), ys
    (x, t), ys = _scan(tallied, (x, zero_tally(stacked)), xs, cfg)
    return x, t, ys


def _decoder_layer(p, x, cfg, *, window, mrope_pos=None, q_offset=0,
                   collect_kv=False, yarn=None):
    x = constrain_batch(x)
    x, kv = _attn_block(p, x, cfg, window=window, mrope_pos=mrope_pos,
                        q_offset=q_offset, yarn=yarn)
    x, aux = _ffn_block(p, x, cfg)
    return x, aux, (kv if collect_kv else None)


# --------------------------------------------------------- forward_train ---

def _scan(body, carry, xs, cfg):
    """Layer scan honouring the remat/scan_group lowering knobs.

    scan_group=u packs u layers into one scan body (plus a python-unrolled
    tail of n % u layers), so differencing cost_analysis at u=1 vs u=2
    isolates true per-layer cost (XLA counts loop bodies once)."""
    if cfg.remat_layers:
        body = jax.checkpoint(body)
    n = jax.tree.leaves(xs)[0].shape[0]
    u = max(1, cfg.scan_group)
    if u == 1:
        return jax.lax.scan(body, carry, xs)

    main = (n // u) * u
    ys_parts = []
    if main:
        xs_main = jax.tree.map(
            lambda a: a[:main].reshape((main // u, u) + a.shape[1:]), xs)

        def grouped(c, xg):
            ys = []
            for i in range(u):
                c, y = body(c, jax.tree.map(lambda a: a[i], xg))
                ys.append(y)
            stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
            return c, stacked

        carry, ys_m = jax.lax.scan(grouped, carry, xs_main)
        # [n//u, u, ...] -> [main, ...]
        ys_m = jax.tree.map(
            lambda a: a.reshape((main,) + a.shape[2:]), ys_m)
        ys_parts.append(ys_m)
    tail_ys = []
    for i in range(main, n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        tail_ys.append(y)
    if tail_ys:
        ys_parts.append(jax.tree.map(lambda *zs: jnp.stack(zs), *tail_ys))
    if len(ys_parts) == 1:
        ys = ys_parts[0]
    elif ys_parts:
        ys = jax.tree.map(lambda *zs: jnp.concatenate(zs, 0), *ys_parts)
    else:
        ys = None
    return carry, ys


def segment_lengths(cfg, kind: str = "train", seq_len: int = 0):
    """Lengths of every layer stack that goes through ``_scan`` for the
    given step kind (train/prefill/decode) -- used by the dry-run's
    counted-layers extrapolation.  seq_len only merges for kind='train'."""
    sl = seq_len if kind == "train" else 0
    if cfg.family in ("dense", "vlm"):
        return [j - i for (i, j, _) in
                _segment_windows(cfg, cfg.n_layers, 0, sl)]
    if cfg.family == "moe":
        out = []
        fkd = cfg.moe.first_k_dense
        if fkd:
            out += [j - i for (i, j, _) in _segment_windows(cfg, fkd, 0, sl)]
        out += [j - i for (i, j, _) in
                _segment_windows(cfg, cfg.n_layers - fkd, fkd, sl)]
        if cfg.mtp and kind == "train":
            pass  # mtp block is python-level (fully counted)
        return out
    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        out, i = [], 0
        while i < cfg.n_layers:
            out.append(min(k, cfg.n_layers - i))
            i += k
        return out
    if cfg.family == "ssm":
        return []                       # python-unrolled: fully counted
    if cfg.family == "audio":
        if kind == "decode":
            return [cfg.n_layers]
        return [cfg.n_enc_layers, cfg.n_layers]
    raise ValueError(cfg.family)


def counted_layers(cfg, u: int, kind: str = "train",
                   seq_len: int = 0) -> int:
    """How many layer instances cost_analysis sees at scan_group=u."""
    tot = 0
    for n in segment_lengths(cfg, kind, seq_len):
        tot += n if n <= u else u + (n % u)
    return tot


def real_layers(cfg, kind: str = "train", seq_len: int = 0) -> int:
    return sum(segment_lengths(cfg, kind, seq_len))


def _scan_decoder_uniform(stacked, x, cfg, window, mrope_pos=None,
                          collect_kv=False, yarn=None):
    """Scan a segment where every layer shares the same (static) window
    and rotary table."""
    def body(carry, lp):
        h, aux = carry
        h, a, kv = _decoder_layer(lp, h, cfg, window=window,
                                  mrope_pos=mrope_pos, collect_kv=collect_kv,
                                  yarn=yarn)
        return (h, aux + a), kv

    (x, aux), kvs = _scan(body, (x, zero_tally(stacked)), stacked, cfg)
    return x, aux, kvs


def _segment_windows(cfg, n_layers, offset=0, seq_len=0):
    """Split [offset, offset+n) into maximal runs of equal window size
    and rotary table (``layer_yarn``).

    When seq_len is given and window >= seq_len, windowed attention equals
    full attention exactly, so segments merge (one scan instead of 2L/pattern
    scans -- vital for llama4's 3:1 iRoPE pattern at train_4k); a full
    layer with YaRN tables never merges with a plain-RoPE one."""
    def kind(i):
        w = 0 if _is_global_layer(cfg, offset + i) else cfg.window
        if w and seq_len and w >= seq_len:
            w = 0
        return w, layer_yarn(cfg, offset + i) is not None
    runs = []
    i = 0
    while i < n_layers:
        k = kind(i)
        j = i
        while j < n_layers and kind(j) == k:
            j += 1
        runs.append((i, j, k[0]))
        i = j
    return runs


def _run_decoder_stack(stacked, x, cfg, n_layers, offset=0, mrope_pos=None,
                       collect_kv=False, seq_len=0):
    """Python-level segmentation into uniform-window runs, scan each.

    seq_len merges window==full segments for training (never for prefill,
    whose KV-cache layout must match ``serve.segment_layout``)."""
    aux = 0.0
    kvs_all = []
    for (i, j, w) in _segment_windows(cfg, n_layers, offset, seq_len):
        seg = jax.tree.map(lambda a: a[i:j], stacked)
        x, a, kvs = _scan_decoder_uniform(seg, x, cfg, w, mrope_pos=mrope_pos,
                                          collect_kv=collect_kv,
                                          yarn=layer_yarn(cfg, offset + i))
        aux = aux + a
        if collect_kv:
            kvs_all.append(kvs)
    return x, aux, kvs_all


def _embed(params, cfg, tokens):
    return params["embed"][tokens]


def _logits(params, cfg, x):
    x = norm(x, params["final_norm"], cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head


def forward_train(params: Params, cfg: ArchConfig, batch) -> tuple:
    """Returns (logits [B, S, V], aux) where aux carries moe/mtp terms."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    aux = {"moe_aux": 0.0}
    x = _embed(params, cfg, tokens)
    mrope_pos = None

    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(x.dtype)   # [B, P, D]
        P = patches.shape[1]
        x = jnp.concatenate([patches, x], axis=1)
        side = max(int(P ** 0.5), 1)
        pt = jnp.zeros((B, P), jnp.int32)
        ph = jnp.broadcast_to((jnp.arange(P) // side)[None], (B, P))
        pw = jnp.broadcast_to((jnp.arange(P) % side)[None], (B, P))
        vis = jnp.stack([pt, ph, pw], axis=0)
        txt = text_mrope_positions(B, S, offset=side)
        mrope_pos = jnp.concatenate([vis, txt], axis=-1)  # [3, B, P+S]

    if cfg.family in ("dense", "vlm"):
        x, a, _ = _run_decoder_stack(params["layers"], x, cfg, cfg.n_layers,
                                     mrope_pos=mrope_pos,
                                     seq_len=x.shape[1])
        aux["moe_aux"] += a
        if cfg.family == "vlm":
            x = x[:, -S:]
        return _logits(params, cfg, x), aux

    if cfg.family == "moe":
        fkd = cfg.moe.first_k_dense
        if fkd:
            x, a, _ = _run_decoder_stack(params["dense_layers"], x, cfg, fkd,
                                         seq_len=x.shape[1])
            aux["moe_aux"] += a
        x, a, _ = _run_decoder_stack(params["moe_layers"], x, cfg,
                                     cfg.n_layers - fkd, offset=fkd,
                                     seq_len=x.shape[1])
        tally = ffnmod.tally_dict(a)
        aux = {**tally, "moe_aux": aux["moe_aux"] + tally["moe_aux"]}
        if cfg.mtp and "mtp" in params:
            # Multi-token prediction: predict t+2 from (h_t, emb(y_{t+1}))
            h = norm(x, params["mtp"]["norm"], cfg.norm)
            nxt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
            mtp_in = jnp.concatenate([h, _embed(params, cfg, nxt)], axis=-1)
            mtp_h = mtp_in @ params["mtp"]["proj"]
            mtp_h, _, _ = _decoder_layer(params["mtp"]["block"], mtp_h, cfg,
                                         window=0)
            aux["mtp_logits"] = _logits(params, cfg, mtp_h)
        return _logits(params, cfg, x), aux

    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        L = cfg.n_layers
        i = 0
        g = 0
        while i < L:
            x, _, _ = _decoder_layer(params["shared_attn"], x, cfg, window=0)
            seg = jax.tree.map(lambda a: a[i:min(i + k, L)],
                               params["mamba_layers"])

            def mamba_body(h, lp):
                h = constrain_batch(h)
                y = ssmmod.mamba2_forward(
                    lp["mamba"], norm(h, lp["ln1"], cfg.norm), cfg)
                return h + y, None

            x, _ = _scan(mamba_body, x, seg, cfg)
            i += k
            g += 1
        return _logits(params, cfg, x), aux

    if cfg.family == "ssm":
        for i, lp in enumerate(params["xlstm_layers"]):
            h = norm(x, lp["ln"], cfg.norm)
            if i in cfg.xlstm.slstm_layers:
                y, _ = ssmmod.slstm_forward(lp["cell"], h, cfg)
            else:
                y, _ = ssmmod.mlstm_forward(lp["cell"], h, cfg)
            x = x + y
        return _logits(params, cfg, x), aux

    if cfg.family == "audio":
        enc = _encode(params, cfg, batch["frame_embeds"])
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
        x, a = _run_encdec_decoder(params, cfg, x, enc)
        aux["moe_aux"] += a
        return _logits(params, cfg, x), aux

    raise ValueError(cfg.family)


def _encode(params, cfg, frame_embeds):
    x = frame_embeds
    F = x.shape[1]
    x = x + sinusoidal_positions(F, cfg.d_model)[None].astype(x.dtype)

    def body(h, lp):
        h = constrain_batch(h)
        hh = norm(h, lp["ln1"], cfg.norm)
        y, _ = attn.gqa_forward(lp["attn"], hh, cfg, causal=False)
        h = h + y
        h, _ = _ffn_block(lp, h, cfg)
        return h, None

    x, _ = _scan(body, x, params["enc_layers"], cfg)
    return norm(x, params["enc_norm"], cfg.norm)


def _enc_kv(lp, enc, cfg):
    B, F, _ = enc.shape
    K, hd = cfg.n_kv_heads, cfg.hd
    h = enc
    k = (h @ lp["cross"]["wk"]).reshape(B, F, K, hd)
    v = (h @ lp["cross"]["wv"]).reshape(B, F, K, hd)
    return k, v


def _run_encdec_decoder(params, cfg, x, enc):
    def body(carry, lp):
        h, aux = carry
        h = constrain_batch(h)
        hh = norm(h, lp["ln1"], cfg.norm)
        y, _ = attn.gqa_forward(lp["attn"], hh, cfg, causal=True)
        h = h + y
        hc = norm(h, lp["ln_cross"], cfg.norm)
        ek, ev = _enc_kv(lp, enc, cfg)
        h = h + attn.gqa_cross_forward(lp["cross"], hc, ek, ev, cfg)
        h, a = _ffn_block(lp, h, cfg)
        return (h, aux + a), None

    (x, aux), _ = _scan(body, (x, 0.0), params["dec_layers"], cfg)
    return x, aux
