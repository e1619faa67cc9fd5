"""Jit'd public wrapper for block attention; resolves GQA + backend routing."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import gqa_ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "backend", "block_q", "block_k", "scale"))
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              backend: str = "ref", block_q: int = 128, block_k: int = 128,
              scale: float | None = None):
    """GQA block attention.

    q: (B, Hq, Sq, d); k, v: (B, Hkv, Skv, d), Hq % Hkv == 0.
    ``window`` > 0 restricts each query to the previous ``window`` keys;
    ``scale`` multiplies the scores (None: 1/sqrt(d)).
    """
    if backend == "ref":
        return gqa_ref(q, k, v, causal=causal, window=window, scale=scale)
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=(backend == "pallas_interpret"))
