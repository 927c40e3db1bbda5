"""Shared building blocks: norms, activations, rotary embeddings, init."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, w, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def norm(x, w, kind: str):
    return rmsnorm(x, w) if kind == "rmsnorm" else layernorm(x, w)


def act_fn(x, kind: str):
    if kind == "sq_relu":
        r = jax.nn.relu(x)
        return r * r
    if kind == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


# ---------------------------------------------------------------- rotary ---

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn):
    """YaRN's inverse frequencies (arXiv:2309.00071, as Hugging Face's
    ``_compute_yarn_parameters`` with ``truncate``): pair ``i`` keeps
    its frequency below the correction range, takes it over ``factor``
    above, and a linear ramp between; the range runs from the dim at
    which ``beta_fast`` rotations fit the original context (floored) to
    the one for ``beta_slow`` (ceiled)."""
    def dim_for(rotations):
        return head_dim * np.log(yarn.original_max
                                 / (rotations * 2 * np.pi)) \
            / (2 * np.log(theta))
    low = max(np.floor(dim_for(yarn.beta_fast)), 0)
    high = min(np.ceil(dim_for(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    base = rope_freqs(head_dim, theta)
    return base / yarn.factor * ramp + base * (1 - ramp)


def apply_rope(x, positions, theta: float, yarn=None):
    """x: [..., S, H, hd]; positions: broadcastable to [..., S].  With
    ``yarn`` (a ``YarnConfig``), YaRN's frequencies, and cos and sin
    scaled by its attention factor."""
    hd = x.shape[-1]
    table = rope_freqs(hd, theta) if yarn is None \
        else yarn_freqs(hd, theta, yarn)
    freqs = jnp.asarray(table, dtype=jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(ang)[..., :, None, :]                        # [..., S, 1, hd/2]
    sin = jnp.sin(ang)[..., :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int):
    """Qwen2-VL M-RoPE: split rotary pairs into (t, h, w) sections."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return (t, h, w)


def apply_mrope(x, pos_thw, theta: float):
    """x: [B, S, H, hd]; pos_thw: [3, B, S] (temporal/height/width ids)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), dtype=jnp.float32)  # [hd/2]
    secs = mrope_sections(hd)
    # angle per section uses the section's position id
    ang_all = pos_thw[..., None].astype(jnp.float32) * freqs  # [3, B, S, hd/2]
    pieces, off = [], 0
    for i, sec in enumerate(secs):
        pieces.append(ang_all[i, ..., off:off + sec])
        off += sec
    ang = jnp.concatenate(pieces, axis=-1)                    # [B, S, hd/2]
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def text_mrope_positions(batch: int, seq: int, offset=0):
    """Plain text: t == h == w == position (matches Qwen2-VL for text)."""
    p = jnp.arange(seq)[None, :] + offset
    p = jnp.broadcast_to(p, (batch, seq))
    return jnp.stack([p, p, p], axis=0)  # [3, B, S]


def sinusoidal_positions(seq: int, d_model: int, offset=0):
    pos = np.arange(seq)[:, None] + offset
    i = np.arange(d_model // 2)[None, :]
    ang = pos / (10000 ** (2 * i / d_model))
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, dtype=jnp.float32)


# ------------------------------------------------------------------ init ---

def dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))
