"""The operations and bytes that each kernel's calls and each RL step
require, from the shapes alone, whatever implements them.  Keyed by name
so that a later kernel on the same path keeps the count: padding, zombie
slots and recomputation are work done, not work required, and are not
counted.  Float32 operands: 4 bytes per element.
"""
from __future__ import annotations

F32 = 4


def _attn_pairs(start: int, stop: int) -> int:
    """Causal (query, key) pairs of queries at positions start..stop-1."""
    return sum(p + 1 for p in range(start, stop))


def flash_attention(*, rows, seq, heads, kv_heads, head_dim, **_):
    """Causal self-attention forward over whole sequences: QK^T and PV
    over the causal pairs; reads q, k, v and writes the output once."""
    pairs = rows * seq * (seq + 1) // 2
    flops = 4 * heads * head_dim * pairs
    nbytes = rows * seq * (2 * heads + 2 * kv_heads) * head_dim * F32
    return flops, nbytes


def paged_attention(*, rows, prompt_len, max_new, heads, kv_heads,
                    head_dim, **_):
    """Decode attention of ``rows`` whole rows: after the prefill, each of
    the ``max_new - 1`` tokens fed back sits at a position p and attends to
    p + 1 cached keys and values, read once per kv head."""
    pairs = _attn_pairs(prompt_len, prompt_len + max_new - 1)
    flops = 4 * heads * head_dim * pairs * rows
    nbytes = rows * (2 * kv_heads * head_dim * F32 * pairs
                     + 2 * heads * head_dim * F32 * (max_new - 1))
    return flops, nbytes


def fused_logprob(*, rows, seq, vocab, **_):
    """The trainer's log-prob of every action position, forward and
    backward: the forward reads the [N, V] logits once, the backward
    reads them again and writes their gradient."""
    n = rows * (seq - 1)
    return 6 * n * vocab, 3 * n * vocab * F32


KERNELS = {"flash_attention": flash_attention,
           "paged_attention": paged_attention,
           "fused_logprob": fused_logprob}


def kernel_work(name: str, **shape):
    return KERNELS[name](**shape)


def roofline_pct(name: str, seconds: float, peak: dict, **shape) -> float:
    """Least time the chip needs for the work, over the time taken, in
    percent.  Above 100 the count or the time is wrong: an error."""
    flops, nbytes = kernel_work(name, **shape)
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    pct = 100.0 * least / seconds
    if pct > 100.0:
        raise ValueError(f"{name}: roofline share {pct:.1f}% > 100%: the "
                         "work is counted too high or the time misses part")
    return pct


def matmul_params(spec) -> int:
    """Weights every token multiplies through: the layers' projections
    and the output head (the embedding is a lookup)."""
    D, H, K, hd, F = (spec["d_model"], spec["n_heads"], spec["n_kv_heads"],
                      spec["head_dim"], spec["d_ff"])
    ffn = (3 if spec["act"] == "silu_gated" else 2) * D * F
    layer = D * (H + 2 * K) * hd + H * hd * D + ffn
    return spec["n_layers"] * layer, D * spec["vocab"]


def rl_row_flops(spec, *, prompt_len, max_new) -> float:
    """Model FLOPs one trained row requires through the loop: its
    prefill (logits at the last prompt position), the ``max_new - 1``
    decode steps that feed its sampled tokens back, and the trainer's
    forward and backward over the whole row."""
    layers, head = matmul_params(spec)
    H, hd, L = spec["n_heads"], spec["head_dim"], spec["n_layers"]
    T = prompt_len + max_new
    prefill = 2 * layers * prompt_len + 2 * head \
        + 4 * L * H * hd * _attn_pairs(0, prompt_len)
    decode = 2 * (layers + head) * (max_new - 1) \
        + 4 * L * H * hd * _attn_pairs(prompt_len, T - 1)
    train = 3 * (2 * (layers + head) * T + 4 * L * H * hd * _attn_pairs(0, T))
    return float(prefill + decode + train)
