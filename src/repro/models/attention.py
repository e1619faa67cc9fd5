"""GQA attention: prefill (full/sliding-window causal, bidirectional),
ring-buffer KV-cache decode, and cross-attention (enc-dec).

Layout conventions:
  hidden x           : (B, S, D)
  q/k/v (internal)   : (B, S, H, hd)
  KV cache per layer : {"k": (B, W, Hkv, hd), "v": same, "pos": (B, W) i32}
where W is the cache window (= seq_len for full attention, = sliding window
for SWA archs / long-context decode). "pos" stores the absolute position
held in each ring slot (-1 = empty), which makes ring-buffer masking exact
from the first token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import attention as flash_attention
from repro.models.common import apply_mrope, apply_rope, dense_init, rmsnorm


def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, *, qkv_bias: bool, qk_norm: bool, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def _project_qkv(p, x, num_heads, num_kv_heads, head_dim, *,
                 qk_norm: bool, rope_theta: float, mrope: bool,
                 positions, x_kv=None):
    """Project and rotate. positions: (B,S) or (3,B,S) when mrope."""
    b, s, _ = x.shape
    xk_src = x if x_kv is None else x_kv
    skv = xk_src.shape[1]
    q = x @ p["wq"]
    k = xk_src @ p["wk"]
    v = xk_src @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, num_heads, head_dim)
    k = k.reshape(b, skv, num_kv_heads, head_dim)
    v = v.reshape(b, skv, num_kv_heads, head_dim)
    if qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if rope_theta and positions is not None:
        if mrope:
            q = apply_mrope(q, positions, rope_theta)
            k = apply_mrope(k, positions, rope_theta)
        else:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attn_prefill(p, x, positions, *, num_heads, num_kv_heads, head_dim,
                 causal: bool = True, window: int = 0,
                 rope_theta: float = 10000.0, qk_norm: bool = False,
                 mrope: bool = False, backend: str = "ref",
                 x_kv=None, return_kv: bool = False, scale=None):
    """Full-sequence attention. x_kv set -> cross-attention (non-causal).
    ``scale`` multiplies the scores (None: 1/sqrt(head_dim))."""
    b, s, d = x.shape
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim,
                           qk_norm=qk_norm, rope_theta=rope_theta,
                           mrope=mrope, positions=positions, x_kv=x_kv)
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        backend=backend, scale=scale)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, num_heads * head_dim)
    out = out @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def init_cache(batch: int, window: int, num_kv_heads: int, head_dim: int,
               dtype):
    return {
        "k": jnp.zeros((batch, window, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, window, num_kv_heads, head_dim), dtype),
        "pos": jnp.full((batch, window), -1, jnp.int32),
    }


def fill_cache(cache, k, v, start: int = 0):
    """Write a prefill's (B, S, Hkv, hd) keys/values into the cache at their
    ring slots (absolute position % window), so subsequent ring-buffer
    decode writes stay aligned."""
    s = k.shape[1]
    w = cache["k"].shape[1]
    assert s <= w, "prefill longer than cache window"
    pos = jnp.arange(s, dtype=jnp.int32) + start
    slots = jnp.mod(pos, w)
    b = k.shape[0]
    return {
        "k": cache["k"].at[:, slots].set(k.astype(cache["k"].dtype)),
        "v": cache["v"].at[:, slots].set(v.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[:, slots].set(
            jnp.broadcast_to(pos[None], (b, s))),
    }


def attn_decode(p, x, cache, cur_index, *, num_heads, num_kv_heads, head_dim,
                window: int = 0, rope_theta: float = 10000.0,
                qk_norm: bool = False, mrope: bool = False, scale=None):
    """One-token decode. x: (B, 1, D); cur_index: scalar i32 (position of
    the new token); ``scale`` multiplies the scores (None:
    1/sqrt(head_dim)). Returns (out (B,1,D), new_cache)."""
    b = x.shape[0]
    w = cache["k"].shape[1]
    if mrope:
        pos1 = jnp.broadcast_to(cur_index, (3, b, 1)).astype(jnp.int32)
    else:
        pos1 = jnp.broadcast_to(cur_index, (b, 1)).astype(jnp.int32)
    q, k_new, v_new = _project_qkv(
        p, x, num_heads, num_kv_heads, head_dim, qk_norm=qk_norm,
        rope_theta=rope_theta, mrope=mrope, positions=pos1)

    slot = jnp.mod(cur_index, w)
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
    pos_cache = jax.lax.dynamic_update_slice(
        cache["pos"],
        jnp.broadcast_to(cur_index, (b, 1)).astype(jnp.int32), (0, slot))

    # grouped-query scores against the whole window
    g = num_heads // num_kv_heads
    qg = q.reshape(b, num_kv_heads, g, head_dim).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)                  # (B, W, Hkv, hd)
    vf = v_cache.astype(jnp.float32)
    scores = jnp.einsum("bngd,bwnd->bngw", qg, kf) * (
        head_dim ** -0.5 if scale is None else scale)
    pos = pos_cache                                   # (B, W)
    valid = (pos >= 0) & (pos <= cur_index)
    if window:
        valid &= pos > cur_index - window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngw,bwnd->bngd", probs, vf)
    out = out.reshape(b, 1, num_heads * head_dim).astype(x.dtype)
    out = out @ p["wo"]
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache}
    return out, new_cache


def cross_attn_kv(p, enc_out, *, num_kv_heads, head_dim):
    """Precompute cross-attention K/V from encoder output (no RoPE)."""
    b, s, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(b, s, num_kv_heads, head_dim)
    v = (enc_out @ p["wv"]).reshape(b, s, num_kv_heads, head_dim)
    return k, v


def cross_attn_apply(p, x, kv, *, num_heads, num_kv_heads, head_dim,
                     backend: str = "ref"):
    """Decoder cross-attention against precomputed encoder K/V."""
    b, s, _ = x.shape
    k, v = kv
    q = (x @ p["wq"]).reshape(b, s, num_heads, head_dim)
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=False, window=0, backend=backend)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, num_heads * head_dim)
    return out @ p["wo"]
