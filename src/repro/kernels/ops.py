"""Jit'd always-Pallas wrappers for the kernels (tests and benchmarks).

These force the Pallas body to execute -- Mosaic-lowered on a TPU or when
``REPRO_KERNEL_MODE=compile``, interpreted elsewhere -- so kernel-parity
tests exercise the kernel semantics no matter what the routing policy
would pick.  Production call sites (trainer loss, reference scoring,
decode sampling, attention) go through ``repro.kernels.dispatch``
instead, which owns the full env/dtype/shape routing between compiled,
interpreted and streamed-jnp backends.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.dispatch import kernel_mode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_logprob import fused_logprob as _logprob
from repro.kernels.fused_sample import fused_sample as _sample
from repro.kernels.int8_matmul import int8_matmul as _int8mm


def _interpret() -> bool:
    mode = kernel_mode()
    if mode == "interpret":
        return True
    return mode != "compile" and jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_t", "block_v"))
def fused_logprob(logits, tokens, block_t: int = 256, block_v: int = 2048):
    return _logprob(logits, tokens, block_t=block_t, block_v=block_v,
                    interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("temperature", "block_b", "block_v"))
def fused_sample(logits, key, temperature: float = 1.0,
                 block_b: int = 256, block_v: int = 2048):
    return _sample(logits, key, temperature=temperature, block_b=block_b,
                   block_v=block_v, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def flash_attention(q, k, v, block_q: int = 256, block_k: int = 256):
    return _flash(q, k, v, block_q=block_q, block_k=block_k,
                  interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k"))
def int8_matmul(x, w_q, scale, block_m: int = 256, block_n: int = 256,
                block_k: int = 512):
    return _int8mm(x, w_q, scale, block_m=block_m, block_n=block_n,
                   block_k=block_k, interpret=_interpret())
