"""Pallas TPU kernel: fused exit-head confidence (online softmax over vocab).

The per-layer exit inference (the paper's lambda_2 cost) computes
``max_c softmax(h @ W)_c`` per sample. Done naively this writes a
``(B, V)`` logits tensor to HBM for every exit (V up to 152k for the
assigned Qwen archs). This kernel streams MXU-aligned vocab tiles of W
through VMEM and keeps only the online (max, sum-exp, argmax) triple per
sample, so HBM traffic is O(B*D + D*V) reads and O(B) writes.

Grid: (num_heads, num_b_tiles, num_v_tiles); the vocab axis is innermost,
so for a fixed (head, batch tile) the vocab sweep is sequential and the
running stats live in VMEM scratch across grid steps (TPU grid iteration
is sequential). The head axis stacks the per-layer exit heads, so every
exit of a multi-exit model is scored by ONE launch (a ``jax.vmap`` over
``pallas_call`` would put the head axis in the block's sublane dimension,
which Mosaic refuses).

TPU tiling: the last two dims of every block are a multiple of (8, 128)
or the whole array dim. The wrapper therefore pads the batch to a whole
number of 8-row tiles, keeps the per-row results as ``(rows, 1)`` columns
(the layout a lane reduction produces) and gives the norm parameters and
the head bias 2-D blocks.

``kind`` selects what happens to the pooled hidden before the matmul:

* ``None``  — it is already normed (the unfused epilogue).
* ``"rmsnorm"`` / ``"layernorm"`` — the fused exit epilogue: the kernel
  takes the RAW pooled hidden plus the exit-norm parameters and applies
  the norm at the first vocab tile, into VMEM scratch, so the whole norm
  -> matmul -> online-softmax epilogue is ONE program launch. Pooling
  commutes with the norm (pooling selects a token, the norm is
  per-token), which is what makes the (B, D) fused form exact.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_V = 512
SUBLANES = 8      # rows per f32 vreg tile: the batch block's granularity

NEG_INF = -1e30
NORM_EPS = 1e-6   # matches models.common rmsnorm/layernorm


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def row_block(b: int, block_b: int) -> int:
    """Rows per batch tile: at most ``block_b``, never more than the batch
    needs, and always a whole number of 8-row sublane tiles."""
    return _round_up(min(block_b, _round_up(b, SUBLANES)), SUBLANES)


def _online_update(logits, vi, m_scr, s_scr, a_scr, *,
                   vocab_size: int, block_v: int):
    """Fold one (bb, bv) logits tile into the running (max, sumexp, argmax).

    Argmax tie-break is pinned to LOWEST-INDEX-WINS: a later tile may take
    the running argmax only on a STRICT improvement (``tile_max > m_prev``),
    and within a tile the smallest maximal column wins — together matching
    the ref oracle's global first-occurrence ``argmax`` even when the max
    ties across tile boundaries (regression test:
    tests/test_kernels_exit_confidence.py, ties straddling ``block_v``).
    """
    # mask vocab padding in the last tile
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < vocab_size, logits, NEG_INF)

    tile_max = jnp.max(logits, axis=-1, keepdims=True)            # (bb, 1)
    # first maximal column, as a lane min-reduction (keeps the (bb, 1)
    # column layout; vocab ids are exact in f32 up to 2**24)
    tile_arg = jnp.min(jnp.where(logits == tile_max,
                                 col.astype(jnp.float32), float(2 ** 24)),
                       axis=-1, keepdims=True).astype(jnp.int32)

    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, tile_max)
    # rescale previous sum and add this tile's contribution
    s_scr[:] = (s_scr[:] * jnp.exp(m_prev - m_new)
                + jnp.sum(jnp.exp(logits - m_new), axis=-1, keepdims=True))
    a_scr[:] = jnp.where(tile_max > m_prev, tile_arg, a_scr[:])
    m_scr[:] = m_new


def _kernel(*refs, vocab_size: int, block_v: int, num_v_tiles: int,
            kind: Optional[str]):
    if kind is None:
        (x_ref, w_ref, hb_ref, conf_ref, pred_ref,
         hbar_scr, m_scr, s_scr, a_scr) = refs
    else:
        (x_ref, g_ref, nb_ref, w_ref, hb_ref, conf_ref, pred_ref,
         hbar_scr, m_scr, s_scr, a_scr) = refs
    vi = pl.program_id(2)

    @pl.when(vi == 0)
    def _init():
        # stage the batch tile ONCE, into VMEM scratch reused by every
        # vocab tile (per-row reductions only)
        x = x_ref[:].astype(jnp.float32)                      # (bb, D)
        if kind is None:
            hbar_scr[:] = x
        else:
            g = g_ref[:].astype(jnp.float32)                  # (1, D)
            if kind == "rmsnorm":
                var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                y = (x * jax.lax.rsqrt(var + NORM_EPS)) * g
            else:
                mu = jnp.mean(x, axis=-1, keepdims=True)
                var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
                y = ((x - mu) * jax.lax.rsqrt(var + NORM_EPS)) * g
            y = y + nb_ref[:].astype(jnp.float32)
            # mirror the unfused epilogue's cast back to the activation
            # dtype (apply_norm returns x.dtype before the matmul)
            hbar_scr[:] = y.astype(x_ref.dtype).astype(jnp.float32)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        s_scr[:] = jnp.zeros_like(s_scr)
        a_scr[:] = jnp.zeros_like(a_scr)

    w = w_ref[:].astype(jnp.float32)                          # (D, bv)
    # f32 products (not one bf16 MXU pass), as the f32 oracle computes
    logits = jax.lax.dot_general(
        hbar_scr[:], w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                   # (bb, bv)
    logits = logits + hb_ref[:].astype(jnp.float32)           # (1, bv)

    _online_update(logits, vi, m_scr, s_scr, a_scr,
                   vocab_size=vocab_size, block_v=block_v)

    @pl.when(vi == num_v_tiles - 1)
    def _finish():
        # max softmax prob = exp(m - logsumexp) = 1 / sum exp(l - m)
        conf_ref[:] = (1.0 / s_scr[:]).astype(conf_ref.dtype)
        pred_ref[:] = a_scr[:]


@functools.partial(jax.jit,
                   static_argnames=("kind", "block_b", "block_v", "interpret"))
def exit_confidence_heads_pallas(x, w, hbias, gamma=None, nbias=None, *,
                                 kind: Optional[str] = None,
                                 block_b: int = DEFAULT_BLOCK_B,
                                 block_v: int = DEFAULT_BLOCK_V,
                                 interpret: bool = False):
    """Exit confidence of G stacked heads in one launch.

    x: (G, B, D) pooled hidden, B a multiple of ``row_block(B, block_b)``
    (the ops wrapper pads); w: (G, D, V) per head or (1, D, V) shared;
    hbias: (1, V) head bias (zeros when absent). With a norm ``kind``,
    gamma/nbias are (G, 1, D) per head or (1, 1, D) shared.
    Returns (conf (G, B, 1) f32, pred (G, B, 1) i32).
    """
    g_n, b, d = x.shape
    gw, d2, v = w.shape
    assert d == d2 and gw in (1, g_n), (x.shape, w.shape)
    assert hbias.shape == (1, v), (hbias.shape, v)
    bb = row_block(b, block_b)
    assert b % bb == 0, (b, bb)
    block_v = v if v < block_v else block_v
    nb = b // bb
    nv = pl.cdiv(v, block_v)

    head_w = (lambda g: g) if gw > 1 else (lambda g: 0)
    in_specs = [pl.BlockSpec((None, bb, d), lambda g, bi, vi: (g, bi, 0))]
    args = [x]
    if kind is not None:
        gg = gamma.shape[0]
        assert gamma.shape == nbias.shape == (gg, 1, d) and gg in (1, g_n), (
            gamma.shape, nbias.shape, x.shape)
        head_g = (lambda g: g) if gg > 1 else (lambda g: 0)
        norm_spec = pl.BlockSpec((None, 1, d),
                                 lambda g, bi, vi: (head_g(g), 0, 0))
        in_specs += [norm_spec, norm_spec]
        args += [gamma, nbias]
    in_specs += [
        pl.BlockSpec((None, d, block_v), lambda g, bi, vi: (head_w(g), 0, vi)),
        pl.BlockSpec((1, block_v), lambda g, bi, vi: (0, vi)),
    ]
    args += [w, hbias]
    out_spec = pl.BlockSpec((None, bb, 1), lambda g, bi, vi: (g, bi, 0))
    kern = functools.partial(_kernel, vocab_size=v, block_v=block_v,
                             num_v_tiles=nv, kind=kind)
    return pl.pallas_call(
        kern,
        grid=(g_n, nb, nv),
        in_specs=in_specs,
        out_specs=(out_spec, out_spec),
        scratch_shapes=(
            pltpu.VMEM((bb, d), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.int32),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((g_n, b, 1), jnp.float32),
            jax.ShapeDtypeStruct((g_n, b, 1), jnp.int32),
        ),
        interpret=interpret,
    )(*args)
