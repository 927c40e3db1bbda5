"""Serving path: KV/state cache construction, prefill, single-token decode.

Cache layout is per-family; attention segments with different window sizes
(llama4 iRoPE) get separate ring buffers sized ``min(cache_len, window)``.
``decode_step`` consumes ONE token against a cache of ``cache_len`` slots --
this is exactly what the decode_32k / long_500k dry-run shapes lower.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import backbone as bb
from repro.models import ssm as ssmmod
from repro.models.common import norm, sinusoidal_positions
from repro.models.ffn import mlp_forward, moe_forward
from repro.models.sharding import constrain_batch

Cache = Dict[str, Any]


def _seg_cache_len(cache_len: int, window: int) -> int:
    return min(cache_len, window) if window else cache_len


def _kv_seg(cfg, n_layers, B, Sc, dtype):
    K, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((n_layers, B, Sc, K, hd), dtype),
        "v": jnp.zeros((n_layers, B, Sc, K, hd), dtype),
        "slot_pos": jnp.full((Sc,), -1, jnp.int32),
    }


def _kv_seg_paged(cfg, n_layers, n_pages, page_size, dtype):
    """Paged arena for one segment: ``n_pages`` allocatable pages of
    ``page_size`` KV slots plus the trash page at index ``n_pages``, each
    slot a token's K kv heads side by side (``[K * hd]``, the layout the
    paged kernel reads).  No ``slot_pos``: validity is per-row (col <= row
    cursor), carried by the page table + ``pos`` vector at the cache top
    level."""
    K, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((n_layers, n_pages + 1, page_size, K * hd), dtype),
        "v": jnp.zeros((n_layers, n_pages + 1, page_size, K * hd), dtype),
    }


def _mla_seg(cfg, n_layers, B, Sc, dtype):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((n_layers, B, Sc, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((n_layers, B, Sc, m.qk_rope_dim), dtype),
        "slot_pos": jnp.full((Sc,), -1, jnp.int32),
    }


def attn_segments(cfg: ArchConfig, n_layers: int, offset: int = 0):
    return bb._segment_windows(cfg, n_layers, offset)


def _attn_stacks(cfg: ArchConfig):
    """(params key, n_layers, first layer's index) of each attention layer
    stack, in the order prefill and decode walk them."""
    if cfg.family == "moe":
        fkd = cfg.moe.first_k_dense
        dense = [("dense_layers", fkd, 0)] if fkd else []
        return dense + [("moe_layers", cfg.n_layers - fkd, fkd)]
    return [("layers", cfg.n_layers, 0)]


def segment_layout(cfg: ArchConfig):
    """Cache segment layout [(n_layers, window), ...] matching the order in
    which prefill/decode walk the (possibly multiple) layer stacks."""
    return [(j - i, w) for _, n, off in _attn_stacks(cfg)
            for (i, j, w) in attn_segments(cfg, n, off)]


def segment_params(params, cfg: ArchConfig):
    """Each attention segment's layers as ``(stacked params, window,
    rotary table)``, in ``segment_layout`` order; a segment that spans
    its whole stack is the stack itself (``a[0:n]`` takes no slice).

    Weights do not change within a decode chunk, so the chunk programs
    take these once, before their step loop, and hand them to every
    ``decode_step``: a slice taken inside the loop is copied out of the
    stack again at every step.  None for the families whose decode walks
    no attention segments."""
    if cfg.family not in ("dense", "vlm", "moe"):
        return None
    return [(jax.tree.map(lambda a: a[i:j], params[key]), w,
             bb.layer_yarn(cfg, off + i))
            for key, n, off in _attn_stacks(cfg)
            for (i, j, w) in attn_segments(cfg, n, off)]


def segment_slices(cfg: ArchConfig) -> int:
    """How many attention segments are a proper sub-range of their layer
    stack: the weight slices ``segment_params`` takes (0 where each stack
    is one segment)."""
    return sum(j - i < n for _, n, off in _attn_stacks(cfg)
               for (i, j, _) in attn_segments(cfg, n, off))


def _moe_tally(cfg, cache, tally):
    """``cache["moe"]`` (pairs held, held experts hit, expert-product
    calls; uint32, wrapping) plus one pass's layer ``tally``."""
    calls = cfg.n_layers - cfg.moe.first_k_dense
    add = jnp.stack([tally[1], tally[2], jnp.float32(calls)])
    return cache["moe"] + add.astype(jnp.uint32)


def init_cache(cfg: ArchConfig, B: int, cache_len: int,
               dtype=jnp.bfloat16, *, layout: str = "dense",
               page_size: int = 0, n_pages: int = 0) -> Cache:
    """An empty cache.  An expert model's cache also carries ``"moe"``:
    what its expert layers computed since (``_moe_tally``), which the
    engine reads back with its harvest."""
    cache: Cache = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.family == "moe":
        cache["moe"] = jnp.zeros((3,), jnp.uint32)
    mk_seg = _mla_seg if cfg.attn_kind == "mla" else _kv_seg

    if layout == "paged":
        from repro.models.paging import paged_blocks
        assert cfg.family in ("dense", "moe") and cfg.attn_kind != "mla", \
            f"paged layout covers dense/moe GQA only, got {cfg.family!r}"
        assert page_size > 0 and n_pages > 0, (page_size, n_pages)
        mb = paged_blocks(cache_len, page_size)
        cache["segments"] = [
            _kv_seg_paged(cfg, n, n_pages, page_size, dtype)
            for (n, _) in segment_layout(cfg)]
        # one table shared by every segment: block b of row r lives in
        # physical page table[r, b] of each segment's arena; the last
        # entry is pinned to the trash page (= n_pages)
        cache["page_table"] = jnp.full((B, mb + 1), n_pages, jnp.int32)
        return cache

    if cfg.family in ("dense", "vlm", "moe"):
        cache["segments"] = [
            mk_seg(cfg, n, B, _seg_cache_len(cache_len, w), dtype)
            for (n, w) in segment_layout(cfg)]
    elif cfg.family == "hybrid":
        k = cfg.shared_attn_every
        n_groups = (cfg.n_layers + k - 1) // k
        cache["mamba"] = jax.vmap(
            lambda _: ssmmod.mamba2_init_state(cfg, B))(
                jnp.arange(cfg.n_layers))
        cache["attn"] = _kv_seg(cfg, n_groups, B,
                                min(cache_len, 4096), dtype)
    elif cfg.family == "ssm":
        states = []
        for i in range(cfg.n_layers):
            if i in cfg.xlstm.slstm_layers:
                states.append(ssmmod.slstm_init_state(cfg, B))
            else:
                states.append(ssmmod.mlstm_init_state(cfg, B))
        cache["xlstm"] = states
    elif cfg.family == "audio":
        F = cfg.frontend_tokens
        K, hd = cfg.n_kv_heads, cfg.hd
        cache["self"] = _kv_seg(cfg, cfg.n_layers, B, cache_len, dtype)
        cache["cross_k"] = jnp.zeros((cfg.n_layers, B, F, K, hd), dtype)
        cache["cross_v"] = jnp.zeros((cfg.n_layers, B, F, K, hd), dtype)
    return cache


# ----------------------------------------------------------------- prefill -

def _write_seg(seg, kvs):
    """Write prefill KVs (stacked [L,B,S,...], positions 0..S-1) into a
    ring segment, position p at slot p % Sc."""
    S = kvs[0].shape[2]
    Sc = seg["slot_pos"].shape[0]
    take = min(S, Sc)
    pos = jnp.arange(S - take, S)
    first = (S - take) % Sc
    out = dict(seg)
    keys = ("ckv", "krope") if "ckv" in seg else ("k", "v")

    # a rotation when the whole ring is rewritten, a slice update from
    # slot 0 otherwise: the TPU compiler aborts on the equivalent scatter
    # pair over the K and V rings
    def write(ring, new, axis):
        if take == Sc:
            return jnp.roll(new, first, axis=axis)
        return jax.lax.dynamic_update_slice_in_dim(ring, new, 0, axis=axis)

    for key_name, kv in zip(keys, kvs):
        out[key_name] = write(
            seg[key_name], kv[:, :, -take:].astype(seg[key_name].dtype), 2)
    out["slot_pos"] = write(seg["slot_pos"], pos.astype(jnp.int32), 0)
    return out


def _prefill_collect(params, cfg, x, mrope_pos=None):
    """Run decoder stacks collecting per-segment stacked KVs; returns
    (x, kvs, the layers' summed tally)."""
    kv_segs = []
    tally = 0.0
    for key, n, off in _attn_stacks(cfg):
        x, a, kvs = bb._run_decoder_stack(params[key], x, cfg, n, offset=off,
                                          mrope_pos=mrope_pos,
                                          collect_kv=True)
        kv_segs.extend(kvs)
        tally = tally + a
    return x, kv_segs, tally


def _extend_collect(params, cfg, x, prefix_kvs, q_offset: int):
    """Prefill *continuation*: run suffix embeds ``x`` (absolute positions
    ``q_offset ..``) through the decoder stacks attending over cached
    prefix KVs, collecting the suffix KVs per segment.

    ``prefix_kvs``: one (k, v) pair per cache segment, each
    [L_seg, B, q_offset, K, hd] gathered from the radix-shared pages.
    Per-query-row attention is independent of the other rows, so the
    result is bit-for-bit what ``_prefill_collect`` computes for the
    same positions of the full prompt.  Also returns the layers' summed
    tally."""
    kv_segs = []
    tally = 0.0
    for (seg, w, yarn), (pk, pv) in zip(segment_params(params, cfg),
                                        prefix_kvs):
        def body(h, inputs, w=w, yarn=yarn):
            lp, pk_l, pv_l = inputs
            h = constrain_batch(h)
            hh = norm(h, lp["ln1"], cfg.norm)
            y, kv = attn.gqa_extend(lp["attn"], hh, pk_l, pv_l, cfg,
                                    q_offset=q_offset, window=w, yarn=yarn)
            h = h + y
            h, a = bb._ffn_block(lp, h, cfg)
            return h, a, kv

        x, t, kvs = bb.scan_tallied(body, x, (seg, pk, pv), cfg, seg)
        kv_segs.append(kvs)
        tally = tally + t
    return x, kv_segs, tally


def prefill(params, cfg: ArchConfig, batch, cache_len: int,
            dtype=jnp.bfloat16):
    """batch: {'tokens': [B, S], optional frontend embeds}.
    Returns (last_logits [B, V], cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = bb._embed(params, cfg, tokens)
    cache = init_cache(cfg, B, cache_len, dtype)
    mrope_pos = None
    prefix = 0

    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(x.dtype)
        P = patches.shape[1]
        prefix = P
        x = jnp.concatenate([patches, x], axis=1)
        side = max(int(P ** 0.5), 1)
        pt = jnp.zeros((B, P), jnp.int32)
        ph = jnp.broadcast_to((jnp.arange(P) // side)[None], (B, P))
        pw = jnp.broadcast_to((jnp.arange(P) % side)[None], (B, P))
        from repro.models.common import text_mrope_positions
        vis = jnp.stack([pt, ph, pw], axis=0)
        txt = text_mrope_positions(B, S, offset=side)
        mrope_pos = jnp.concatenate([vis, txt], axis=-1)

    if cfg.family in ("dense", "vlm", "moe"):
        x, kv_segs, tally = _prefill_collect(params, cfg, x,
                                             mrope_pos=mrope_pos)
        cache["segments"] = [
            _write_seg(seg, kvs)
            for seg, kvs in zip(cache["segments"], kv_segs)]
        if "moe" in cache:
            cache["moe"] = _moe_tally(cfg, cache, tally)
        cache["pos"] = jnp.asarray(S + prefix, jnp.int32)
        return bb._logits(params, cfg, x[:, -1]), cache

    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        L = cfg.n_layers
        mamba_states, attn_kvs = [], []
        i = 0
        while i < L:
            h = norm(x, params["shared_attn"]["ln1"], cfg.norm)
            y, kv = (attn.gqa_forward(params["shared_attn"]["attn"], h, cfg)
                     if cfg.attn_kind != "mla" else (None, None))
            x = x + y
            x, _ = bb._ffn_block(params["shared_attn"], x, cfg)
            attn_kvs.append(kv)
            seg = jax.tree.map(lambda a: a[i:min(i + k, L)],
                               params["mamba_layers"])

            def mamba_body(h, lp):
                h = constrain_batch(h)
                y, st = ssmmod.mamba2_forward(
                    lp["mamba"], norm(h, lp["ln1"], cfg.norm), cfg,
                    return_state=True)
                return h + y, st

            x, sts = bb._scan(mamba_body, x, seg, cfg)
            mamba_states.append(sts)
            i += k
        cache["mamba"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *mamba_states)
        kv_k = jnp.stack([kv[0] for kv in attn_kvs])   # [G,B,S,K,hd]
        kv_v = jnp.stack([kv[1] for kv in attn_kvs])
        cache["attn"] = _write_seg(cache["attn"], (kv_k, kv_v))
        cache["pos"] = jnp.asarray(S, jnp.int32)
        return bb._logits(params, cfg, x[:, -1]), cache

    if cfg.family == "ssm":
        states = []
        for i, (lp, st0) in enumerate(zip(params["xlstm_layers"],
                                          cache["xlstm"])):
            h = norm(x, lp["ln"], cfg.norm)
            if i in cfg.xlstm.slstm_layers:
                y, st = ssmmod.slstm_forward(lp["cell"], h, cfg)
            else:
                y, st = ssmmod.mlstm_forward(lp["cell"], h, cfg)
            x = x + y
            states.append(st)
        cache["xlstm"] = states
        cache["pos"] = jnp.asarray(S, jnp.int32)
        return bb._logits(params, cfg, x[:, -1]), cache

    if cfg.family == "audio":
        enc = bb._encode(params, cfg, batch["frame_embeds"])
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)

        def body(carry, lp):
            h = carry
            hh = norm(h, lp["ln1"], cfg.norm)
            y, kv = attn.gqa_forward(lp["attn"], hh, cfg, causal=True)
            h = h + y
            hc = norm(h, lp["ln_cross"], cfg.norm)
            ek, ev = bb._enc_kv(lp, enc, cfg)
            h = h + attn.gqa_cross_forward(lp["cross"], hc, ek, ev, cfg)
            h, _ = bb._ffn_block(lp, h, cfg)
            return h, (kv[0], kv[1], ek, ev)

        x, (ks, vs, eks, evs) = bb._scan(body, x, params["dec_layers"], cfg)
        cache["self"] = _write_seg(cache["self"], (ks, vs))
        cache["cross_k"], cache["cross_v"] = eks, evs
        cache["pos"] = jnp.asarray(S, jnp.int32)
        return bb._logits(params, cfg, x[:, -1]), cache

    raise ValueError(cfg.family)


# ------------------------------------------------------------ decode_step -

def _decode_seg(stacked_params, seg, x, pos, cfg, window, mrope_pos=None,
                yarn=None):
    """Scan one attention segment during decode; returns (x, new segment,
    the layers' summed tally)."""
    if "ckv" in seg:
        def body(h, inputs):
            lp, ckv, krope = inputs
            h = constrain_batch(h)
            hh = norm(h, lp["ln1"], cfg.norm)
            y, ckv, krope, sp = attn.mla_decode(
                lp["attn"], hh, ckv, krope, seg["slot_pos"], pos, cfg)
            h = h + y
            h, a = bb._ffn_block(lp, h, cfg)
            return h, a, (ckv, krope, sp)

        x, tally, (ckv, krope, sps) = bb.scan_tallied(
            body, x, (stacked_params, seg["ckv"], seg["krope"]), cfg,
            stacked_params)
        new_seg = {"ckv": ckv, "krope": krope, "slot_pos": sps[0]}
        return x, new_seg, tally

    def body(h, inputs):
        lp, ck, cv = inputs
        h = constrain_batch(h)
        hh = norm(h, lp["ln1"], cfg.norm)
        y, ck, cv, sp = attn.gqa_decode(lp["attn"], hh, ck, cv,
                                        seg["slot_pos"], pos, cfg,
                                        window=window, mrope_pos=mrope_pos,
                                        yarn=yarn)
        h = h + y
        h, a = bb._ffn_block(lp, h, cfg)
        return h, a, (ck, cv, sp)

    x, tally, (ck, cv, sps) = bb.scan_tallied(
        body, x, (stacked_params, seg["k"], seg["v"]), cfg, stacked_params)
    new_seg = {"k": ck, "v": cv, "slot_pos": sps[0]}
    return x, new_seg, tally


def _decode_seg_paged(stacked_params, seg, x, page_table, pos, cfg, window,
                      yarn=None):
    """Scan one attention segment during paged decode: every layer
    scatters its new KV into the row's mapped page and attends through
    the page table (``dispatch.paged_attention``).  Returns (x, new
    segment, the layers' summed tally)."""
    def body(h, inputs):
        lp, ak, av = inputs
        h = constrain_batch(h)
        hh = norm(h, lp["ln1"], cfg.norm)
        y, ak, av = attn.gqa_decode_paged(lp["attn"], hh, ak, av,
                                          page_table, pos, cfg,
                                          window=window, yarn=yarn)
        h = h + y
        h, a = bb._ffn_block(lp, h, cfg)
        return h, a, (ak, av)

    x, tally, (ak, av) = bb.scan_tallied(
        body, x, (stacked_params, seg["k"], seg["v"]), cfg, stacked_params)
    return x, {"k": ak, "v": av}, tally


def decode_step(params, cfg: ArchConfig, cache: Cache, tokens,
                segments=None):
    """tokens: [B, 1].  Returns (logits [B, V], new cache).

    ``segments``: ``segment_params(params, cfg)``, taken once by a caller
    that runs many steps on the same weights; taken here when absent."""
    pos = cache["pos"]
    x = bb._embed(params, cfg, tokens)
    B = tokens.shape[0]
    mrope_pos = None
    if cfg.family == "vlm":
        P = cfg.frontend_tokens
        side = max(int(P ** 0.5), 1)
        tp = jnp.broadcast_to((side + pos - P)[None, None], (B, 1))
        mrope_pos = jnp.stack([tp, tp, tp], axis=0)

    if cfg.family in ("dense", "vlm", "moe"):
        if segments is None:
            segments = segment_params(params, cfg)
        paged = "page_table" in cache
        new_segs = []
        tally = 0.0
        for (lp, w, yarn), seg in zip(segments, cache["segments"]):
            if paged:
                x, new_seg, t = _decode_seg_paged(
                    lp, seg, x, cache["page_table"], pos, cfg, w, yarn=yarn)
            else:
                x, new_seg, t = _decode_seg(lp, seg, x, pos, cfg, w,
                                            mrope_pos=mrope_pos, yarn=yarn)
            new_segs.append(new_seg)
            tally = tally + t
        new_cache = {"pos": pos + 1, "segments": new_segs}
        if paged:
            new_cache["page_table"] = cache["page_table"]
        if "moe" in cache:
            new_cache["moe"] = _moe_tally(cfg, cache, tally)
        return bb._logits(params, cfg, x[:, -1]), new_cache

    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        L = cfg.n_layers
        new_mamba, new_attn_k, new_attn_v = [], [], []
        sp_out = cache["attn"]["slot_pos"]
        i, g = 0, 0
        while i < L:
            hh = norm(x, params["shared_attn"]["ln1"], cfg.norm)
            y, ck, cv, sp_out = attn.gqa_decode(
                params["shared_attn"]["attn"], hh,
                cache["attn"]["k"][g], cache["attn"]["v"][g],
                cache["attn"]["slot_pos"], pos, cfg)
            x = x + y
            x, _ = bb._ffn_block(params["shared_attn"], x, cfg)
            new_attn_k.append(ck)
            new_attn_v.append(cv)
            lp_seg = jax.tree.map(lambda a: a[i:min(i + k, L)],
                                  params["mamba_layers"])
            st_seg = jax.tree.map(lambda a: a[i:min(i + k, L)],
                                  cache["mamba"])

            def body(h, inputs):
                lp, st = inputs
                h = constrain_batch(h)
                y, st = ssmmod.mamba2_decode(
                    lp["mamba"], norm(h, lp["ln1"], cfg.norm), st, cfg)
                return h + y, st

            x, new_st = bb._scan(body, x, (lp_seg, st_seg), cfg)
            new_mamba.append(new_st)
            i += k
            g += 1
        new_cache = {
            "pos": pos + 1,
            "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                  *new_mamba),
            "attn": {"k": jnp.stack(new_attn_k), "v": jnp.stack(new_attn_v),
                     "slot_pos": sp_out},
        }
        return bb._logits(params, cfg, x[:, -1]), new_cache

    if cfg.family == "ssm":
        states = []
        for i, (lp, st) in enumerate(zip(params["xlstm_layers"],
                                         cache["xlstm"])):
            h = norm(x, lp["ln"], cfg.norm)
            if i in cfg.xlstm.slstm_layers:
                y, st = ssmmod.slstm_decode(lp["cell"], h, st, cfg)
            else:
                y, st = ssmmod.mlstm_decode(lp["cell"], h, st, cfg)
            x = x + y
            states.append(st)
        new_cache = dict(cache)
        new_cache["pos"] = pos + 1
        new_cache["xlstm"] = states
        return bb._logits(params, cfg, x[:, -1]), new_cache

    if cfg.family == "audio":
        x = x + _sin_pos_at(pos, cfg.d_model).astype(x.dtype)

        def body(carry, inputs):
            h, sp = carry
            lp, ck, cv, xk, xv = inputs
            h = constrain_batch(h)
            hh = norm(h, lp["ln1"], cfg.norm)
            y, ck, cv, sp = attn.gqa_decode(lp["attn"], hh, ck, cv, sp, pos,
                                            cfg)
            h = h + y
            hc = norm(h, lp["ln_cross"], cfg.norm)
            h = h + attn.gqa_cross_forward(lp["cross"], hc, xk, xv, cfg)
            h, _ = bb._ffn_block(lp, h, cfg)
            return (h, sp), (ck, cv)

        (x, sp), (ks, vs) = bb._scan(
            body, (x, cache["self"]["slot_pos"]),
            (params["dec_layers"], cache["self"]["k"], cache["self"]["v"],
             cache["cross_k"], cache["cross_v"]), cfg)
        new_cache = dict(cache)
        new_cache["pos"] = pos + 1
        new_cache["self"] = {"k": ks, "v": vs, "slot_pos": sp}
        return bb._logits(params, cfg, x[:, -1]), new_cache

    raise ValueError(cfg.family)


def _sin_pos_at(pos, d_model):
    import numpy as np
    i = jnp.arange(d_model // 2)
    ang = pos.astype(jnp.float32) / (10000 ** (2 * i / d_model))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None, :]


# ------------------------------------------------ engine slot-pool helpers -

class SlotPool:
    """Host-side occupancy tracking for the batch axis of a running
    decode cache: which rows are live and which are free for admission.
    Pure bookkeeping -- the device arrays never shrink; a freed slot is
    simply overwritten by the next ``stitch_cache_row``."""

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self._free = list(range(n_slots - 1, -1, -1))    # pop() -> slot 0
        self._used: set = set()

    def acquire(self):
        """Claim a free slot index, or None when the pool is full."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._used.add(slot)
        return slot

    def release(self, slot: int) -> None:
        assert slot in self._used, f"slot {slot} not in use"
        self._used.discard(slot)
        self._free.append(slot)

    @property
    def used(self):
        return frozenset(self._used)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def __len__(self) -> int:
        return len(self._used)


def assert_engine_cache(cfg: ArchConfig, layout: str = "dense") -> None:
    """Which cache families the engine's per-row decode cursors support.

    Dense layout needs dense-family KV rings that never wrap: unwindowed
    segments only (a windowed ring is shorter than the sequence, so
    slots alias across rows) and non-MLA caches.  The paged layout's
    per-row page tables remove the shared-``slot_pos`` constraint, so
    windowed segments (llama4 iRoPE ring families) are admitted there --
    masking enforces the window; per-page reclamation of slid-past
    windows stays a paged follow-up.  MLA latent caches (need latent-
    shaped pages) and ssm/hybrid/vlm state families (no KV pages at all)
    stay rejected under both layouts."""
    assert cfg.family in ("dense", "moe"), \
        f"engine needs a dense-family KV cache, got family={cfg.family!r} " \
        "(ssm/hybrid state caches are not paged KV; vlm needs mrope decode)"
    assert cfg.attn_kind != "mla", \
        "engine does not support MLA latent caches yet " \
        "(paged follow-up: latent-shaped pages for ckv/krope)"
    if layout == "paged":
        return
    for (_, w) in segment_layout(cfg):
        assert not w, \
            "engine needs unwindowed rings: a windowed segment wraps, " \
            "which breaks the shared slot_pos across per-row cursors " \
            "(use the paged layout -- per-row page tables admit windows)"


@jax.jit
def stitch_cache_row(cache: Cache, row_cache: Cache, slot) -> Cache:
    """Graft a freshly-prefilled B=1 cache into batch row ``slot`` of a
    running per-row-cursor cache (prefill-into-slot admission).

    ``cache["pos"]`` must be a [B] vector of per-row cursors; the
    donor's scalar ``pos`` becomes the admitted row's cursor.
    ``slot_pos`` merges with ``maximum``: under the engine's
    no-wraparound invariant both sides hold -1 or the slot's own index,
    so the union is exact.  ``slot`` is traced, so admissions into
    different slots share one compilation."""
    slot = jnp.asarray(slot)
    new_segs = []
    for seg, rseg in zip(cache["segments"], row_cache["segments"]):
        out = dict(seg)
        for name in ("k", "v"):
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                seg[name], rseg[name].astype(seg[name].dtype), slot, axis=1)
        out["slot_pos"] = jnp.maximum(seg["slot_pos"], rseg["slot_pos"])
        new_segs.append(out)
    new_cache = {"pos": cache["pos"].at[slot].set(row_cache["pos"]),
                 "segments": new_segs}
    if "moe" in cache:
        new_cache["moe"] = cache["moe"] + row_cache["moe"]
    return new_cache
