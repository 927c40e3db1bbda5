"""Paged-attention decode kernel: one query token over a paged KV arena.

The paged layout (``models/paging.py``) stores KV in a fixed arena of
``n_pages + 1`` pages of ``P`` token slots (the last page is the trash
page), each page ``[P, K * hd]``: a token's K kv heads side by side, the
layout the kernel reads.  Each batch row owns a page table of
``max_blocks + 1`` physical page ids mapping logical block ``b`` -> arena
page.  Decode attends one query per row against the row's mapped pages
only -- O(max_blocks * P) per row regardless of arena size, which is what
lets one arena back hundreds of concurrent rows.

Two implementations behind ``repro.kernels.dispatch.paged_attention``:

* ``paged_attention_ref`` -- gather-then-attend in pure jnp, written to
  be *bit-for-bit identical* to the dense per-row ``gqa_decode`` path
  when the logical lengths match: the per-row page-table gather
  reassembles exactly the [B, S, K, hd] tensor the dense ring holds
  (garbage in not-yet-written slots is masked to ``NEG_INF`` whose
  ``exp`` underflows to exact 0.0), then runs the identical einsum /
  softmax / einsum sequence.  This is the ``jnp`` route and the parity
  oracle for the engine suite.
* ``paged_attention_kernel`` -- Pallas, one program per row over all kv
  heads.  The page table and per-row cursors are scalar-prefetched; the
  arena stays in HBM and the program copies ``block_pages`` pages at a
  time into VMEM with its own double-buffered DMAs, from the first page
  the window reaches to the page that holds the cursor, with
  online-softmax (m, l, acc) scratch.  Pages past the cursor are neither
  fetched nor computed, and the gathered [B, S, K, hd] intermediate never
  exists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM for one row's double-buffered K and V blocks
_BLOCK_VMEM_BYTES = 1 << 20


def block_pages(P: int, K: int, hd: int, max_blocks: int,
                itemsize: int = 4) -> int:
    """Pages per DMA block: the most whose double-buffered K and V blocks
    fit ``_BLOCK_VMEM_BYTES``, at most ``max_blocks``."""
    page_bytes = P * K * hd * itemsize
    return max(1, min(max_blocks, _BLOCK_VMEM_BYTES // (4 * page_bytes)))


def paged_attention_ref(q, arena_k, arena_v, page_table, pos, *,
                        window: int = 0):
    """q: [B, H, hd]; arena_[kv]: [n_pages + 1, P, K * hd];
    page_table: [B, max_blocks + 1] int32 (last entry trash, unread);
    pos: [B] int32 decode cursor per row -> [B, H, hd].

    Mirrors the dense ``gqa_decode`` math operation-for-operation
    (same einsum strings, f32 accumulation, softmax over the same
    logical axis) so paged == dense bitwise when S matches the ring.
    """
    B, H, hd = q.shape
    P, K = arena_k.shape[1], arena_k.shape[2] // hd
    g = H // K
    mb = page_table.shape[1] - 1
    S = mb * P
    ks = arena_k[page_table[:, :mb]].reshape(B, S, K, hd)
    vs = arena_v[page_table[:, :mb]].reshape(B, S, K, hd)
    qh = q.reshape(B, 1, K, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qh, ks,
                        preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(S)
    posb = pos[:, None]
    mask = cols[None, :] <= posb
    if window:
        mask &= cols[None, :] > posb - window
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    y = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(vs.dtype), vs)
    return y.reshape(B, H, hd)


def _kernel(pt_ref, pos_ref, q_ref, ak_hbm, av_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
            P: int, ppb: int, mb: int, scale: float, window: int):
    b = pl.program_id(0)
    K, hd = q_ref.shape[1], q_ref.shape[3]
    T = ppb * P

    def pages(row):
        """First and last page holding a column the row attends."""
        pos = pos_ref[row]
        last = jnp.minimum(pos // P, mb - 1)
        if not window:
            return 0, last
        return jnp.minimum(jnp.maximum(pos - window + 1, 0) // P, last), last

    def each_copy(row, blk, slot, act):
        """``act`` on the K and V copy of each page of block ``blk`` that
        the row attends, into buffer ``slot``."""
        first, last = pages(row)
        for i in range(ppb):
            page = blk * ppb + i

            @pl.when((page >= first) & (page <= last))
            def _():
                phys = pt_ref[row, page]
                for hbm, buf, kv in ((ak_hbm, k_buf, 0), (av_hbm, v_buf, 1)):
                    act(pltpu.make_async_copy(
                        hbm.at[phys], buf.at[slot, pl.ds(i * P, P)],
                        sems.at[kv, slot]))

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    first, last = pages(b)

    # grid steps run in order: row b's first block was started by row
    # b - 1's last iteration, so only row 0 starts its own
    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        each_copy(b, first // ppb, 0, start)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    pos = pos_ref[b]

    def attended(col):
        # a cursor clamped to max_blocks * P attends every column
        ok = col <= jnp.minimum(pos, mb * P - 1)
        return ok & (col > pos - window) if window else ok

    def body(j, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(j < last // ppb)
        def _():
            each_copy(b, j + 1, nxt, start)

        @pl.when((j == last // ppb) & (b + 1 < pl.num_programs(0)))
        def _():
            each_copy(b + 1, pages(b + 1)[0] // ppb, nxt, start)

        each_copy(b, j, slot, wait)
        mask = attended(j * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1))
        # pages the row does not attend were not copied: zero their
        # values so that no stale buffer contents reach p @ v
        row_mask = attended(
            j * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0))
        for h in range(K):
            q = q_ref[0, h].astype(jnp.float32)                  # [g, hd]
            k = k_buf[slot, :, h * hd:(h + 1) * hd]              # [T, hd]
            v = jnp.where(row_mask, v_buf[slot, :, h * hd:(h + 1) * hd],
                          0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s * scale, NEG_INF)              # [g, T]
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a fully-masked block (the window slid past it) keeps m at
            # NEG_INF; exp(s - m) would be exp(0) there, so re-zero it
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(first // ppb, last // ppb + 1, body, 0)
    for h in range(K):
        denom = jnp.maximum(l_ref[h], 1e-30)
        o_ref[0, h] = (acc_ref[h] / denom).astype(o_ref.dtype)


def paged_attention_kernel(q, arena_k, arena_v, page_table, pos, *,
                           window: int = 0, interpret: bool = False):
    """Pallas paged decode: same contract as ``paged_attention_ref``.

    Grid (B,): one program per row over all kv heads.  ``page_table`` and
    ``pos`` ride in as scalar prefetch; the arena stays in HBM and each
    program DMAs ``block_pages`` of its own pages at a time (each from
    ``table[row, block]``) into a double-buffered VMEM block, the next
    row's first block in flight while the last one computes.
    """
    B, H, hd = q.shape
    P, K = arena_k.shape[1], arena_k.shape[2] // hd
    g = H // K
    mb = page_table.shape[1] - 1
    ppb = block_pages(P, K, hd, mb, arena_k.dtype.itemsize)
    qh = q.reshape(B, K, g, hd)
    row_block = pl.BlockSpec((1, K, g, hd),
                             lambda b, pt_ref, pos_ref: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row_block,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_block,
        scratch_shapes=[
            pltpu.VMEM((2, ppb * P, K * hd), arena_k.dtype),
            pltpu.VMEM((2, ppb * P, K * hd), arena_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((K, g, 1), jnp.float32),
            pltpu.VMEM((K, g, 1), jnp.float32),
            pltpu.VMEM((K, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, P=P, ppb=ppb, mb=mb, scale=hd ** -0.5,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      qh, arena_k, arena_v)
    return out.reshape(B, H, hd)
