"""Mamba2 (SSD) block for the Zamba2 hybrid architecture.

Chunked state-space-duality formulation in pure jnp: within a chunk the
token mixing is an attention-like masked contraction (MXU-friendly), between
chunks a sequential ``lax.scan`` carries the (H, P, N) state. All decay
exponents are differences of a non-increasing cumulative log-decay, so every
``exp`` argument is <= 0 (no overflow by construction).

Streaming state per layer = (conv state (B, K-1, conv_dim), ssm state
(B, H, P, N)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init

CONV_K = 4
HEAD_DIM = 64


def init_mamba2(key, d_model: int, state_size: int, expand: int, dtype):
    d_inner = expand * d_model
    nheads = d_inner // HEAD_DIM
    n = state_size
    conv_dim = d_inner + 2 * n * 1  # x + B + C streams (single group)
    ks = jax.random.split(key, 5)
    return {
        # in_proj -> [z (d_inner), xBC (conv_dim), dt (nheads)]
        "w_in": dense_init(ks[0], d_model, d_inner + conv_dim + nheads, dtype),
        "conv_w": (jax.random.normal(ks[1], (CONV_K, conv_dim), jnp.float32)
                   * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.zeros((nheads,), jnp.float32),   # A = -exp(a_log)
        "dt_bias": jnp.full((nheads,), -2.0, jnp.float32),
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "w_out": dense_init(ks[4], d_inner, d_model, dtype),
    }


def _causal_conv(x, w, b, conv_state):
    """x: (B, S, C); depthwise causal conv, kernel K. conv_state: (B, K-1, C)."""
    xpad = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    new_state = xpad[:, -(CONV_K - 1):, :]
    out = sum(xpad[:, i:i + x.shape[1], :] * w[i] for i in range(CONV_K))
    return jax.nn.silu(out + b), new_state


def _ssd_chunked(xh, bmat, cmat, dt, a, h0, chunk: int):
    """Chunked SSD scan.

    xh: (B, S, H, P); bmat/cmat: (B, S, N); dt: (B, S, H) (post-softplus);
    a: (H,) negative; h0: (B, H, P, N). Returns (y (B,S,H,P), hT)."""
    b, s, h, p = xh.shape
    n = bmat.shape[-1]
    nc = s // chunk
    assert nc * chunk == s, (s, chunk)
    xh = xh.reshape(b, nc, chunk, h, p)
    bm = bmat.reshape(b, nc, chunk, n)
    cm = cmat.reshape(b, nc, chunk, n)
    dtc = dt.reshape(b, nc, chunk, h)

    loga = dtc * a[None, None, None, :]                 # (B, NC, C, H) <= 0
    cum = jnp.cumsum(loga, axis=2)                      # inclusive cumlog

    def chunk_step(hprev, inp):
        xc, bc, cc, dc, cumc = inp
        # hprev: (B, H, P, N)
        # inter-chunk: y_t += (C_t . h_prev) * exp(cum_t)  (y_t = C_t h_t,
        # h_t carries the full inclusive decay product back to h_0)
        dec_q = jnp.exp(cumc)                           # (B, C, H)
        y_inter = jnp.einsum("bcn,bhpn,bch->bchp", cc, hprev, dec_q)
        # intra-chunk attention-like term
        # M[t,s] = (C_t . B_s) exp(cum_{t-1} - cum_s... ) dt_s for s <= t-? SSD
        # uses s <= t with decay exp(cum_t - cum_s) and dt_s weighting
        qk = jnp.einsum("btn,bsn->bts", cc, bc)         # (B, C, C)
        dec = cumc[:, :, None, :] - cumc[:, None, :, :]  # (B, t, s, H)
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        dec = jnp.where(mask[None, :, :, None], dec, -jnp.inf)
        m = qk[:, :, :, None] * jnp.exp(dec) * dc[:, None, :, :]
        y_intra = jnp.einsum("btsh,bshp->bthp", m, xc)
        # state update: h' = exp(cum_C) h + sum_s exp(cum_C - cum_s) dt_s B_s x_s^T
        dec_last = jnp.exp(cumc[:, -1:, :] - cumc)      # (B, C, H)
        upd = jnp.einsum("bch,bch,bcn,bchp->bhpn",
                         dec_last, dc, bc, xc)
        hnew = jnp.exp(cumc[:, -1])[:, :, None, None] * hprev + upd
        return hnew, y_inter + y_intra

    xs = (xh.transpose(1, 0, 2, 3, 4), bm.transpose(1, 0, 2, 3),
          cm.transpose(1, 0, 2, 3), dtc.transpose(1, 0, 2, 3),
          cum.transpose(1, 0, 2, 3))
    hT, ys = jax.lax.scan(chunk_step, h0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, p)
    return y, hT


def mamba2_forward(p, x, state, *, state_size: int, expand: int,
                   chunk: int = 128, norm_eps: float = 1e-6):
    """x: (B, S, D); state: {"conv": (B,K-1,C), "ssm": (B,H,P,N)}.
    ``norm_eps`` is the gated RMSNorm's epsilon."""
    b, s, d = x.shape
    d_inner = expand * d
    nheads = d_inner // HEAD_DIM
    n = state_size
    zxbcdt = x @ p["w_in"]
    z, xbc, dt_raw = jnp.split(
        zxbcdt, [d_inner, d_inner + d_inner + 2 * n], axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], state["conv"])
    xs, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"])                # (B, S, H)
    a = -jnp.exp(p["a_log"])                            # (H,) negative
    xh = xs.reshape(b, s, nheads, HEAD_DIM).astype(jnp.float32)
    if s == 1:
        # decode: exact single recurrence step
        loga = dt[:, 0] * a[None]                       # (B, H)
        dec = jnp.exp(loga)
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0],
                         bmat[:, 0].astype(jnp.float32), xh[:, 0])
        hnew = dec[:, :, None, None] * state["ssm"] + upd
        y = jnp.einsum("bn,bhpn->bhp", cmat[:, 0].astype(jnp.float32), hnew)
        y = y[:, None]                                  # (B, 1, H, P)
    else:
        pad = (-s) % chunk
        if pad:
            xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
            cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        y, hnew = _ssd_chunked(xh, bmat.astype(jnp.float32),
                               cmat.astype(jnp.float32), dt, a,
                               state["ssm"], chunk)
        y = y[:, :s]
    y = y + p["d_skip"][None, None, :, None] * xh[:, :s]
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    # gated RMSNorm (Mamba2 style)
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + norm_eps)
         * p["norm_scale"].astype(jnp.float32)).astype(x.dtype)
    out = y @ p["w_out"]
    return out, {"conv": new_conv.astype(jnp.float32), "ssm": hnew}


def init_mamba2_state(batch: int, d_model: int, state_size: int, expand: int):
    d_inner = expand * d_model
    nheads = d_inner // HEAD_DIM
    conv_dim = d_inner + 2 * state_size
    return {
        "conv": jnp.zeros((batch, CONV_K - 1, conv_dim), jnp.float32),
        "ssm": jnp.zeros((batch, nheads, HEAD_DIM, state_size), jnp.float32),
    }
