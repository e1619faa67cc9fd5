"""Shared building blocks: norms, RoPE / M-RoPE, init helpers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- init utils

def dense_init(key, fan_in: int, fan_out: int, dtype):
    scale = fan_in ** -0.5
    return (jax.random.normal(key, (fan_in, fan_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------- norms

def rmsnorm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


def init_norm(key, d: int, kind: str, dtype):
    del key
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def apply_norm(x, p, kind: str, eps: float = 1e-6):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"], eps)
    return layernorm(x, p["scale"], p["bias"], eps)


# ---------------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int):
    """Qwen2-VL style (t, h, w) sections over the half-dim.

    hd=128 -> (16, 24, 24), matching the Qwen2-VL config; scales down
    proportionally for smoke variants."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return (t, h, w)


def apply_mrope(x, positions3, theta: float):
    """M-RoPE: x (B, S, H, hd); positions3 (3, B, S) = (t, h, w) streams."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_freqs(hd, theta)                       # (half,)
    secs = mrope_sections(hd)
    # build per-frequency position source: freq slot j uses stream chosen by
    # which section j falls into
    sec_id = jnp.concatenate([
        jnp.full((secs[0],), 0, jnp.int32),
        jnp.full((secs[1],), 1, jnp.int32),
        jnp.full((secs[2],), 2, jnp.int32),
    ])                                                   # (half,)
    # positions3: (3, B, S) -> select per freq: (B, S, half)
    pos = jnp.take(positions3, sec_id, axis=0)           # (half, B, S)
    pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)   # (B, S, half)
    ang = pos * freqs                                    # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(logits, labels, valid=None):
    """Mean CE in f32. logits (..., C); labels (...) int32.

    The label logit is extracted with a one-hot contraction rather than
    ``take_along_axis``: a gather over a vocab-sharded logits tensor makes
    GSPMD all-gather the full (B, S, V) — the one-hot multiply keeps the
    sharding (reduce over the sharded axis becomes a cheap psum)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    ll = jnp.sum(logits * onehot, axis=-1)
    loss = lse - ll
    if valid is not None:
        loss = loss * valid
        return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1.0)
    return jnp.mean(loss)
