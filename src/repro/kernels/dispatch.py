"""Unified kernel dispatch: one routing layer for every compute hot path.

Replaces the ad-hoc ``INTERPRET`` flag that used to live in ``ops.py``.
Every caller (trainer loss, reference scoring, decode sampling, dense-causal
attention, the expert layer's products) goes through the public entry
points here -- ``token_logprob`` / ``sample`` / ``attention`` /
``paged_attention`` / ``grouped_matmul`` / ``int8_matmul`` -- and the
routing policy picks one of three backends per call site from platform,
env, dtype and static shapes (``auto``: compiled kernels above the size
thresholds on a TPU, streamed jnp everywhere else):

* ``pallas_compile``   -- Mosaic-lowered Pallas kernels (TPU).
* ``pallas_interpret`` -- the Pallas interpreter (bit-accurate kernel
  semantics with jax ops; CI parity runs, no Mosaic).
* ``jnp``              -- streamed pure-jnp fallbacks (lax.scan over vocab /
  KV tiles; lowering-safe for the 512-device dry-run, and the fast path on
  the CPU dev box).

All three backends stream vocabulary tiles with online ``(max, sumexp)``
accumulators: none materializes a full-vocab fp32 log-softmax, which is the
trainer's peak-memory hot spot at V = 256k (paper Sec. 6).

Env knobs (read at trace time):
  REPRO_KERNEL_MODE       auto | compile | interpret | ref
  REPRO_PALLAS_COMPILE=1  legacy alias for REPRO_KERNEL_MODE=compile
  REPRO_KERNEL_MIN_VOCAB  min vocab before a compiled kernel is used (4096)
  REPRO_KERNEL_MIN_SEQ    min seq len before a compiled kernel is used (512)
  REPRO_LOGPROB_BLOCK_T/V, REPRO_SAMPLE_BLOCK_B/V, REPRO_ATTN_BLOCK
"""
from __future__ import annotations

import collections
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_logprob import fused_logprob, fused_logprob_bwd
from repro.kernels.fused_sample import fused_sample, gumbel_noise, \
    key_data_u32
from repro.kernels.int8_matmul import int8_matmul as _int8mm
from repro.kernels.online import NEG_INF, online_softmax_step

_PALLAS_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)

# (hot path, backend) -> traces; a process-wide record of what was staged,
# read by routes_taken (generator and trainer threads trace concurrently)
_ROUTES: collections.Counter = collections.Counter()
_ROUTES_LOCK = threading.Lock()
# (P, K, hd, max_blocks) -> pages per DMA block of each paged-attention
# shape staged on a kernel route, read by paged_block_pages
_BLOCK_PAGES: dict = {}


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def kernel_mode() -> str:
    """Resolved mode: auto | compile | interpret | ref."""
    m = os.environ.get("REPRO_KERNEL_MODE", "").strip().lower()
    if m in ("compile", "interpret", "ref", "auto"):
        return m
    if m:
        # a typo like "pallas"/"compiled" must not silently fall back to
        # the jnp path on TPU -- that is an unbounded perf regression
        raise ValueError(
            f"REPRO_KERNEL_MODE={m!r}: expected compile|interpret|ref|auto")
    if os.environ.get("REPRO_PALLAS_COMPILE", "0") == "1":
        return "compile"
    return "auto"


def _route(path: str, n: int, dtype, threshold_var, default_min: int) -> str:
    """Pick a backend for hot path ``path`` whose dominant streamed axis
    has size n (no size threshold where ``threshold_var`` is None), and
    count the choice (at trace time: once per compiled call site, not per
    call) for ``routes_taken``."""
    backend = _pick(n, dtype, threshold_var, default_min)
    with _ROUTES_LOCK:
        _ROUTES[(path, backend)] += 1
    return backend


def _pick(n: int, dtype, threshold_var: str, default_min: int) -> str:
    mode = kernel_mode()
    if mode == "ref" or dtype not in _PALLAS_DTYPES:
        return "jnp"
    if mode == "interpret":
        return "pallas_interpret"
    # auto compiles the kernels where Mosaic exists (TPU); elsewhere the
    # streamed-jnp path both lowers and beats the Pallas interpreter.
    # Below the threshold kernel launch overhead dominates, so jnp.
    wants = mode == "compile" or jax.default_backend() == "tpu"
    if wants and (threshold_var is None
                  or n >= _env_int(threshold_var, default_min)):
        return "pallas_compile"
    return "jnp"


def _per_device(kernel):
    """Mosaic kernels cannot be partitioned automatically.  Traced under
    a multi-device mesh (an executor on its submesh, ``jax.set_mesh``),
    the kernel runs on every device over whole, replicated operands;
    elsewhere it is called as is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def routes_taken() -> dict:
    """``{path: {backend: traces}}``: which backend each hot path was
    traced with so far in this process."""
    out: dict = {}
    with _ROUTES_LOCK:
        for (path, backend), n in _ROUTES.items():
            out.setdefault(path, {})[backend] = n
    return out


#: the backends in the order ``route_code`` numbers them
BACKENDS = ("jnp", "pallas_interpret", "pallas_compile")


def route_code(path: str):
    """The backend hot path ``path`` was staged on, as its index in
    ``BACKENDS`` (the highest if several), or None if never staged."""
    taken = routes_taken().get(path)
    return max(BACKENDS.index(b) for b in taken) if taken else None


def paged_block_pages() -> dict:
    """``{(P, K, hd, max_blocks): pages per block}`` of each paged
    attention shape staged on a kernel route so far in this process."""
    with _ROUTES_LOCK:
        return dict(_BLOCK_PAGES)


# ------------------------------------------------------- token logprob ---

def _stream_tile(arr, j, start_size, rows):
    """Clamped [rows, bv] vocab tile at block j: the last tile is shifted
    back to stay in bounds, and `valid` marks the columns this block owns
    (the clamp overlap belongs to the previous block)."""
    bv, V = start_size
    start = jnp.minimum(j * bv, V - bv)
    tile = jax.lax.dynamic_slice(arr, (0, start), (rows, bv))
    cols = start + jnp.arange(bv)
    return tile.astype(jnp.float32), start, cols, (cols >= j * bv)[None, :]


def _logprob_stream_jnp(logits, tokens, bv: int):
    """Streamed log pi(token): lax.scan over [T, bv] vocab tiles with online
    (m, s) accumulators.  Returns (logprobs [T] f32, m [T], log_s [T])."""
    T, V = logits.shape
    bv = min(bv, V)
    n = -(-V // bv)

    def body(carry, j):
        m, s, tval = carry
        tile, start, _, valid = _stream_tile(logits, j, (bv, V), T)
        m_new, s, _ = online_softmax_step(m, s, tile, valid)
        local = jnp.clip(tokens - start, 0, bv - 1)
        vals = jnp.take_along_axis(tile, local[:, None], axis=1)[:, 0]
        in_blk = (tokens >= start) & (tokens < start + bv)
        return (m_new, s, jnp.where(in_blk, vals, tval)), None

    init = (jnp.full((T,), NEG_INF), jnp.zeros((T,)),
            jnp.full((T,), NEG_INF))
    (m, s, tval), _ = jax.lax.scan(body, init, jnp.arange(n))
    log_s = jnp.log(s)
    # subtract m before log s: with extreme logits (|m| ~ 1e30) the combined
    # logZ = m + log s absorbs log s entirely in fp32
    return (tval - m) - log_s, m, log_s


def _logprob_bwd_stream_jnp(logits, tokens, m, log_s, g, bv: int):
    """Streamed VJP: d logits = g * (onehot - softmax), written tile-by-tile
    into the (unavoidable) [T, V] output; softmax is rebuilt from the saved
    online stats so no full-vocab fp32 intermediate exists besides the
    output."""
    T, V = logits.shape
    bv = min(bv, V)
    n = -(-V // bv)
    cols = jnp.arange(bv)

    def body(dl, j):
        tile, start, _, _ = _stream_tile(logits, j, (bv, V), T)
        p = jnp.exp((tile - m[:, None]) - log_s[:, None])
        onehot = (cols[None, :] == (tokens - start)[:, None])
        d = (onehot.astype(jnp.float32) - p) * g[:, None]
        # clamp overlap recomputes identical values, so the re-write is safe
        return jax.lax.dynamic_update_slice(
            dl, d.astype(dl.dtype), (0, start)), None

    dl, _ = jax.lax.scan(body, jnp.zeros_like(logits), jnp.arange(n))
    return dl


def _logprob_fwd_impl(logits, tokens, backend: str, bt: int, bv: int):
    if backend == "jnp":
        return _logprob_stream_jnp(logits, tokens, bv)
    out, m, s = _per_device(functools.partial(
        fused_logprob, block_t=bt, block_v=bv,
        interpret=backend != "pallas_compile", return_stats=True))(
            logits, tokens)
    return out, m, jnp.log(s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _token_logprob_2d(logits, tokens, backend: str, bt: int, bv: int):
    return _logprob_fwd_impl(logits, tokens, backend, bt, bv)[0]


def _token_logprob_2d_fwd(logits, tokens, backend, bt, bv):
    out, m, log_s = _logprob_fwd_impl(logits, tokens, backend, bt, bv)
    return out, (logits, tokens, m, log_s)


def _token_logprob_2d_bwd(backend, bt, bv, res, g):
    logits, tokens, m, log_s = res
    if backend == "jnp":
        dl = _logprob_bwd_stream_jnp(logits, tokens, m, log_s, g, bv)
    else:
        dl = _per_device(functools.partial(
            fused_logprob_bwd, block_t=bt, block_v=bv,
            interpret=backend != "pallas_compile"))(
                logits, tokens, m, log_s, g)
    return dl, None


_token_logprob_2d.defvjp(_token_logprob_2d_fwd, _token_logprob_2d_bwd)


def token_logprob(logits, tokens, *, block_t: int = 0, block_v: int = 0):
    """log softmax(logits)[token] per position, differentiable, streamed.

    logits: [..., V] (f32/bf16); tokens: [...] int -> [...] f32.  Forward
    saves the online (m, s) stats; backward rebuilds softmax tile-by-tile
    from logZ (grad is ``(onehot - softmax) * g``), so neither direction
    materializes a full-vocab fp32 log-softmax.
    """
    V = logits.shape[-1]
    lead = logits.shape[:-1]
    bt = block_t or _env_int("REPRO_LOGPROB_BLOCK_T", 256)
    bv = min(block_v or _env_int("REPRO_LOGPROB_BLOCK_V", 2048), V)
    backend = _route("logprob", V, logits.dtype, "REPRO_KERNEL_MIN_VOCAB",
                     4096)
    T = 1
    for d in lead:
        T *= d
    out = _token_logprob_2d(logits.reshape(T, V),
                            tokens.reshape(T).astype(jnp.int32),
                            backend, bt, bv)
    return out.reshape(lead)


# ------------------------------------------------------------- sampling ---

def _sample_stream_jnp(logits, key, temperature: float, bv: int):
    """Streamed Gumbel-max: same online (m, s) + running-argmax recurrence as
    the Pallas kernel, over lax.scan vocab tiles; identical tokens by
    construction (shared counter-based noise)."""
    B, V = logits.shape
    bv = min(bv, V)
    n = -(-V // bv)
    k0, k1 = key_data_u32(key)
    inv = 1.0 / temperature if temperature > 0.0 else 1.0
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, bv))

    def body(carry, j):
        m, s, best, btok, blog = carry
        tile, start, cols, valid = _stream_tile(logits, j, (bv, V), B)
        tile = tile * inv
        m_new, s, masked = online_softmax_step(m, s, tile, valid)
        z = masked
        if temperature > 0.0:
            z = z + gumbel_noise(rows, jnp.broadcast_to(cols[None], (B, bv)),
                                 k0, k1)
        z = jnp.where(valid, z, -jnp.inf)
        tile_best = jnp.max(z, axis=-1)
        tile_arg = jnp.argmax(z, axis=-1).astype(jnp.int32)
        better = tile_best > best
        chosen = jnp.take_along_axis(tile, tile_arg[:, None], axis=1)[:, 0]
        return (m_new, s, jnp.maximum(best, tile_best),
                jnp.where(better, start + tile_arg, btok),
                jnp.where(better, chosen, blog)), None

    init = (jnp.full((B,), NEG_INF), jnp.zeros((B,)),
            jnp.full((B,), -jnp.inf), jnp.zeros((B,), jnp.int32),
            jnp.full((B,), NEG_INF))
    (m, s, _, tok, blog), _ = jax.lax.scan(body, init, jnp.arange(n))
    return tok, (blog - m) - jnp.log(s)


def sample(logits, key, temperature: float, *, block_v: int = 0):
    """Categorical draw + behavior logprob in one streamed pass.

    logits: [B, V]; returns (tokens [B] int32, log mu(token) [B] f32) under
    the temperature-scaled sampling distribution (greedy argmax scored at
    T=1 when ``temperature == 0``).
    """
    B, V = logits.shape
    bv = min(block_v or _env_int("REPRO_SAMPLE_BLOCK_V", 2048), V)
    bb = _env_int("REPRO_SAMPLE_BLOCK_B", 256)
    backend = _route("sample", V, logits.dtype, "REPRO_KERNEL_MIN_VOCAB",
                     4096)
    if backend == "jnp":
        return _sample_stream_jnp(logits, key, temperature, bv)
    return _per_device(functools.partial(
        fused_sample, temperature=temperature, block_b=bb, block_v=bv,
        interpret=backend != "pallas_compile"))(logits, key)


# ------------------------------------------------------------ attention ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_vjp(q, k, v, block: int, compiled: bool):
    return _flash_padded(q, k, v, block, compiled)


def _flash_padded(q, k, v, block: int, compiled: bool):
    S = q.shape[1]
    b = min(block, S)
    pad = (-S) % b
    if pad:
        # zero-padded KV columns sit at positions > every real row, so the
        # causal mask already excludes them; padded query rows are sliced off
        wid = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, wid), jnp.pad(k, wid), jnp.pad(v, wid)
    out = _per_device(functools.partial(
        _flash, block_q=b, block_k=b, interpret=not compiled))(q, k, v)
    return out[:, :S]


def _flash_vjp_fwd(q, k, v, block, compiled):
    return _flash_padded(q, k, v, block, compiled), (q, k, v)


def _flash_vjp_bwd(block, compiled, res, g):
    # recompute-based backward through the chunked flash pattern: identical
    # math to the forward kernel, O(S * block) live scores, lowers everywhere
    from repro.models.attention import chunked_attention
    q, k, v = res
    _, vjp_fn = jax.vjp(
        lambda q_, k_, v_: chunked_attention(q_, k_, v_, causal=True), q, k, v)
    return vjp_fn(g)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              block_q: int = 512, q_offset: int = 0, kv_positions=None,
              unroll: bool = False):
    """Training/prefill attention: Pallas flash kernel for dense-causal
    self-attention segments, chunked-jnp fallback for everything else
    (windowed, cross, MLA's asymmetric head dims, prefill continuations).

    q: [B, Sq, H, hd]; k/v: [B, Sk, K, hd(v)] -> [B, Sq, H, hd(v)].
    """
    from repro.models.attention import chunked_attention
    Sq, H = q.shape[1], q.shape[2]
    Sk, K = k.shape[1], k.shape[2]
    eligible = (causal and not window and q_offset == 0
                and kv_positions is None and Sq == Sk
                and v.shape[-1] == q.shape[-1] and H % K == 0)
    backend = _route("attention", Sq, q.dtype, "REPRO_KERNEL_MIN_SEQ",
                     512) if eligible else "jnp"
    if backend == "jnp":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 block_q=block_q, q_offset=q_offset,
                                 kv_positions=kv_positions, unroll=unroll)
    return _flash_vjp(q, k, v, _env_int("REPRO_ATTN_BLOCK", 256),
                      backend == "pallas_compile")


def paged_attention(q, arena_k, arena_v, page_table, pos, *, window: int = 0):
    """Paged decode attention: one query per row against the row's page
    table over a shared KV arena (``models/paging.py`` layout).

    q: [B, H, hd]; arena_[kv]: [n_pages + 1, P, K * hd] (a
    ``[n_pages + 1, P, K, hd]`` arena is viewed so); page_table:
    [B, max_blocks + 1] int32; pos: [B] int32 -> [B, H, hd].  Routing
    follows the attention seq-len threshold on the row's *logical*
    length ``max_blocks * P`` (what one program actually streams); the
    jnp route is the gather reference that is bitwise-equal to dense
    ``gqa_decode``, the kernel route DMAs each row's pages from the table
    without materializing the gather.
    """
    from repro.kernels.paged_attention import (block_pages,
                                               paged_attention_kernel,
                                               paged_attention_ref)
    if arena_k.ndim == 4:     # as bench/record_trace.py holds it
        arena_k = arena_k.reshape(*arena_k.shape[:2], -1)
        arena_v = arena_v.reshape(*arena_v.shape[:2], -1)
    P, hd, mb = arena_k.shape[1], q.shape[-1], page_table.shape[1] - 1
    backend = _route("paged_attention", mb * P, q.dtype,
                     "REPRO_KERNEL_MIN_SEQ", 512)
    if backend == "jnp":
        return paged_attention_ref(q, arena_k, arena_v, page_table, pos,
                                   window=window)
    shape = (P, arena_k.shape[2] // hd, hd, mb)
    with _ROUTES_LOCK:
        _BLOCK_PAGES[shape] = block_pages(*shape, arena_k.dtype.itemsize)
    return _per_device(functools.partial(
        paged_attention_kernel, window=window,
        interpret=backend != "pallas_compile"))(
            q, arena_k, arena_v, page_table, pos)


# ------------------------------------------------------- grouped matmul ---

def _gmm_kernel_call(lhs, rhs, sizes, tm, compiled, transpose_rhs=False):
    from repro.kernels.grouped_matmul import gmm, tile_groups
    tg, nu = tile_groups(sizes, tm, lhs.shape[0] // tm)
    return _per_device(functools.partial(
        gmm, tm=tm, transpose_rhs=transpose_rhs,
        interpret=not compiled))(lhs, rhs, tg, nu)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm_vjp(lhs, rhs, sizes, tm: int, compiled: bool):
    return _gmm_kernel_call(lhs, rhs, sizes, tm, compiled)


def _gmm_vjp_fwd(lhs, rhs, sizes, tm, compiled):
    return _gmm_kernel_call(lhs, rhs, sizes, tm, compiled), (lhs, rhs, sizes)


def _gmm_vjp_bwd(tm, compiled, res, g):
    from repro.kernels.grouped_matmul import tgmm, tile_groups
    lhs, rhs, sizes = res
    d_lhs = _gmm_kernel_call(g, rhs, sizes, tm, compiled,
                             transpose_rhs=True)
    tg, nu = tile_groups(sizes, tm, lhs.shape[0] // tm)
    d_rhs = _per_device(functools.partial(
        tgmm, tm=tm, interpret=not compiled))(lhs, g, sizes, tg, nu)
    return d_lhs, d_rhs.astype(rhs.dtype), None


_gmm_vjp.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def grouped_matmul(lhs, rhs, sizes, *, tm: int):
    """The expert product over tile-aligned groups
    (``kernels/grouped_matmul.py``'s layout): lhs [M, K] holds group g's
    ``sizes[g]`` rows from a multiple of ``tm``; rhs [G, K, N] ->
    [M, N], differentiable in lhs and rhs.

    The kernel route (TPU) runs the Pallas ``gmm`` forward and ``gmm``
    against the transposed weights plus ``tgmm`` backward, over the
    tiles in use only; rows past them are left unwritten.  The jnp route
    is ``jax.lax.ragged_dot`` (which the CPU computes densely).  On the
    kernel route a weight with no column band that fits the kernel's
    VMEM budget, either way round, is an error."""
    from repro.kernels.grouped_matmul import col_tile, gmm_ref
    backend = _route("grouped_matmul", lhs.shape[0], lhs.dtype, None, 0)
    if backend == "jnp":
        return gmm_ref(lhs, rhs, sizes, tm=tm)
    K, N = rhs.shape[1:]
    if not (col_tile(K, N, rhs.dtype.itemsize)
            and col_tile(N, K, rhs.dtype.itemsize)):
        raise ValueError(f"grouped_matmul: no column band of a {K}x{N} "
                         f"{rhs.dtype} weight fits the kernel's VMEM budget")
    return _gmm_vjp(lhs, rhs, sizes, tm, backend == "pallas_compile")


# --------------------------------------------------------------- matmul ---

def int8_matmul(x, w_q, scale, *, block_m: int = 256, block_n: int = 256,
                block_k: int = 512):
    """Quantized matmul: Pallas kernel when the mode asks for it,
    dequantize-then-dot otherwise.  (Dispatch surface for the int8 kernel;
    today's generator quantization dequantizes once at weight sync via
    ``ddma.quantize_dequant``, so only tests/benchmarks hit this yet.)"""
    backend = _route("int8_matmul", x.shape[-1], x.dtype,
                     "REPRO_KERNEL_MIN_MATMUL", 1024)
    if backend == "jnp":
        from repro.kernels.ref import int8_matmul_ref
        return int8_matmul_ref(x, w_q, scale)
    return _per_device(functools.partial(
        _int8mm, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=backend != "pallas_compile"))(x, w_q, scale)
