"""Plain building blocks of the reference forward passes.

Nothing here imports the program. Every matrix product accumulates in
float32 at ``Precision.HIGHEST``. A `Numerics` states the rounding a
configuration's file gives for its reference and for its control: the
operands of each product (``mm``) and each stored activation (``act``)
are rounded to that type first, and everything is computed in float32
from the rounded values. ``mm="bf16"`` with ``act="f32"`` is what a TPU
does with float32 at XLA's default precision (one bfloat16 pass,
accumulated in float32).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn


def round_to(x, kind: str, axis: int = -1):
    """``x`` rounded to ``kind`` and returned in float32.

    ``fp8`` is float8 e4m3 with one scale per slice along ``axis`` (the
    absolute maximum maps to 448), as a quantized matmul would store it."""
    x = x.astype(jnp.float32)
    if kind == "f32":
        return x
    if kind == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if kind == "fp8":
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return q * scale
    raise ValueError(f"unknown rounding {kind!r}")


@dataclasses.dataclass(frozen=True)
class Numerics:
    """Rounding of matmul operands (``mm``) and of stored activations
    (``act``): ``f32`` for the reference, narrower for a control."""
    mm: str = "f32"
    act: str = "f32"

    def matmul(self, a, w):
        """``a @ w``: activations rounded per row, weights per column."""
        a = round_to(a, self.mm, axis=-1)
        w = round_to(w, self.mm, axis=0)
        return self.store(jnp.matmul(a, w, precision=HIGHEST))

    def einsum(self, spec, a, b):
        a = round_to(a, self.mm, axis=-1)
        b = round_to(b, self.mm, axis=-1)
        return self.store(jnp.einsum(spec, a, b, precision=HIGHEST))

    def store(self, x):
        return round_to(x, self.act)


REFERENCE = Numerics()


def layernorm(x, scale, bias, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rmsnorm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta: float):
    """Rotary embedding on the two halves of the head dim.
    x: (B, P, H, hd); positions: (P,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(num: Numerics, q, k, v, mask):
    """Softmax attention. q: (B, P, H, hd); k, v: (B, P, Hkv, hd), the
    Hkv heads shared by H / Hkv query heads each; mask: broadcastable to
    (B, H, P, P), True where a query may read a key."""
    rep = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = num.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = num.store(jax.nn.softmax(scores, axis=-1))
    return num.einsum("bhqk,bkhd->bqhd", probs, v)
