"""Pallas TPU kernel for the RWKV6 WKV recurrence (chunked scan).

TPU adaptation of the CUDA wkv kernel: the grid is (B*H, T/C) with the
chunk axis innermost, so for each (batch, head) the chunks run sequentially
and the (dk, dv) state lives in VMEM scratch across chunk steps — the HBM
traffic is exactly one read of (r, k, v, w) and one write of y, with the
state never leaving VMEM. Within a chunk the recurrence is evaluated by a
``fori_loop`` of exact rank-1 updates (VPU); a production variant would use
the chunked matmul (flash-linear-attention) form on the MXU — that variant
trades exactness of the decay products for MXU throughput and is noted in
DESIGN.md. Correctness here is bit-faithful to ref.py in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sfin_ref, s_scr, *,
            chunk: int, num_chunks: int, seq_len: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)

    u = u_ref[0, 0].astype(jnp.float32)                  # (dk,)

    def step(t, s):
        rt = r_ref[0, t].astype(jnp.float32)             # (dk,)
        kt = k_ref[0, t].astype(jnp.float32)
        vt = v_ref[0, t].astype(jnp.float32)             # (dv,)
        wt = w_ref[0, t].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]                   # (dk, dv)
        y = jnp.sum((s + u[:, None] * kv) * rt[:, None], axis=0)
        # positions beyond seq_len (padded final chunk) must not update state
        valid = (ci * chunk + t) < seq_len
        y_ref[0, t] = jnp.where(valid, y, 0.0).astype(y_ref.dtype)
        s_new = wt[:, None] * s + kv
        return jnp.where(valid, s_new, s)

    s = jax.lax.fori_loop(0, chunk, step, s_scr[:])
    s_scr[:] = s

    @pl.when(ci == num_chunks - 1)
    def _finish():
        sfin_ref[0] = s_scr[:].astype(sfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, *, chunk: int = DEFAULT_CHUNK,
                interpret: bool = False):
    """r,k,w: (B, H, T, dk); v: (B, H, T, dv); u: (H, dk).

    Returns (y (B, H, T, dv) f32, final_state (B, H, dk, dv) f32)."""
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    nc = pl.cdiv(t, chunk)
    bh = b * h

    def flat(x):
        return x.reshape(bh, t, x.shape[-1])

    # (H, 1, dk): the block's last two dims are then the array's own, as
    # TPU tiling requires; grid row g = batch * H + head reads head g % H
    u3 = u[:, None, :]

    kern = functools.partial(_kernel, chunk=chunk, num_chunks=nc, seq_len=t)
    y, sfin = pl.pallas_call(
        kern,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, dk), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, dv), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, dk), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, 1, dk), lambda g, ci: (g % h, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, dv), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, dk, dv), lambda g, ci: (g, 0, 0)),
        ),
        scratch_shapes=(pltpu.VMEM((dk, dv), jnp.float32),),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ),
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(w), u3)
    return y.reshape(b, h, t, dv), sfin.reshape(b, h, dk, dv)
