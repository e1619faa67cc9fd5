"""granite-4.0-h-micro: weights from the seed, the plain float32 reference of
the served tokens, and the useful operations of prefill and decode.

The model is IBM's Granite 4.0-H Micro (``granitemoehybrid`` with no
experts): 40 layers, each an RMSNorm, its mixer, then an RMSNorm and a
SwiGLU MLP, every branch multiplied by ``residual_multiplier`` before its
residual add. The mixer of layer l is named by ``layer_types[l]``:

- attention: grouped-query attention with no positional encoding,
  ``softmax(q k^T * attention_multiplier) v``, then ``o_proj``;
- mamba: ``[z, xBC, dt] = x W_in``; ``xBC = silu(causal_conv4(xBC) + b)``,
  split into ``x, B, C``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head ``S <- exp(dt A) S + dt (x outer B)`` and
  ``y = S C + D x``; then ``rms(y * silu(z)) * w_norm`` over all channels
  (one group) and ``W_out``.

The embedding is multiplied by ``embedding_multiplier``; the final answer
is ``rms(h) W_head / logits_scaling``. Each layer's early exit (SplitEE's,
not part of the published model) is ``rms_l(h) W_head / logits_scaling``
with the same head.

Served decode leaves holes: a token that exits at layer d does not run the
layers above d, unless it was offloaded and the cloud completed them. The
reference reproduces that at each layer. An attention layer masks the keys
it never computed. A Mamba layer runs its recurrence token by token and
gives a token it never computed ``dt = 0`` (decay 1, no update), and its
conv window holds the last inputs that layer computed, so the token passes
it by. Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import ref_ops

HIGHEST = jax.lax.Precision.HIGHEST


def dims(spec):
    D, H = spec["hidden_size"], spec["num_attention_heads"]
    Hm, P = spec["mamba_n_heads"], spec["mamba_d_head"]
    N, G = spec["mamba_d_state"], spec["mamba_n_groups"]
    return dict(L=spec["num_hidden_layers"], D=D, H=H,
                Hkv=spec["num_key_value_heads"], hd=D // H,
                F=spec["intermediate_size"], V=spec["vocab_size"],
                Hm=Hm, P=P, N=N, K=spec["mamba_d_conv"], G=G,
                d_in=Hm * P, conv=Hm * P + 2 * G * N,
                kinds=tuple(spec["layer_types"]))


def seed_key(seed: int):
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(spec, seed: int):
    """The weights, drawn on the device in one jitted call from ``seed``,
    in the program's parameter layout and served type (bfloat16; the
    Mamba2 decay, step bias and skip are float32, as in the program)."""
    k = dims(spec)
    return _make(tuple(sorted((n, v) for n, v in k.items())),
                 jnp.dtype(spec["torch_dtype"]), seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(items, dtype, key):
    k = dict(items)
    L, D, H, Hkv, hd, F, V = (k[n] for n in ("L", "D", "H", "Hkv", "hd",
                                              "F", "V"))
    Hm, N, K, d_in, conv = k["Hm"], k["N"], k["K"], k["d_in"], k["conv"]
    La = k["kinds"].count("attention")
    Lm = L - La
    ks = iter(jax.random.split(key, 32))

    def normal(shape):
        return jax.random.normal(next(ks), shape, jnp.float32)

    def dense(shape):
        return (normal(shape) * shape[-2] ** -0.5).astype(dtype)

    def scale(shape):
        return (1.0 + 0.1 * normal(shape)).astype(dtype)

    # Mamba2's own initialisation: A in [1, 16], dt in [1e-3, 1e-1]
    a = jax.random.uniform(next(ks), (Lm, Hm), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(ks), (Lm, Hm), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    mamba = {
        "w_in": dense((Lm, D, 2 * d_in + 2 * k["G"] * N + Hm)),
        "conv_w": (0.3 * normal((Lm, K, conv))).astype(dtype),
        "conv_b": (0.1 * normal((Lm, conv))).astype(dtype),
        "a_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "d_skip": 1.0 + 0.1 * normal((Lm, Hm)),
        "norm_scale": scale((Lm, d_in)),
        "w_out": dense((Lm, d_in, D)),
    }
    attention = {"wq": dense((La, D, H * hd)), "wk": dense((La, D, Hkv * hd)),
                 "wv": dense((La, D, Hkv * hd)), "wo": dense((La, H * hd, D))}
    layers = {
        "ln1": {"scale": scale((L, D))},
        "ln2": {"scale": scale((L, D))},
        "mlp": {"wi": dense((L, D, F)), "wg": dense((L, D, F)),
                "wo": dense((L, F, D))},
        "exit_norm": {"scale": scale((L, D))},
    }
    return {
        "embed": (0.02 * normal((V, D))).astype(dtype),
        "layers": layers,
        "mixers": {"mamba": mamba, "attention": attention},
        "final_norm": {"scale": scale((D,))},
        "exit_w": dense((D, V)),
    }


def _tables(kinds):
    is_attn = np.array([t == "attention" for t in kinds])
    slot = np.where(is_attn, np.cumsum(is_attn), np.cumsum(~is_attn)) - 1
    return jnp.asarray(is_attn), jnp.asarray(slot, jnp.int32)


@functools.partial(jax.jit, static_argnums=(0, 6))
def _forward(static, params, tokens, valid, out_pos, head_layer,
             num: ref_ops.Numerics):
    (kinds, H, Hkv, Hm, N, K, eps, emb_mult, res_mult, att_mult,
     logit_div) = static
    f32 = lambda a: num.store(a.astype(jnp.float32))  # noqa: E731
    x = num.store(f32(jnp.take(params["embed"], tokens, axis=0)) * emb_mult)
    B, P, D = x.shape
    L = valid.shape[1]
    pos = jnp.arange(P)
    causal = pos[None, :] <= pos[:, None]                   # (q, k)
    mix = params["mixers"]

    def rms(v, s):
        return num.store(ref_ops.rmsnorm(v, f32(s), eps))

    def attention(h, j, ok):
        a = jax.tree.map(lambda w: w[j], mix["attention"])
        hd = a["wq"].shape[-1] // H
        q = num.matmul(h, f32(a["wq"])).reshape(B, P, H, hd)
        k = num.matmul(h, f32(a["wk"])).reshape(B, P, Hkv, hd)
        v = num.matmul(h, f32(a["wv"])).reshape(B, P, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        scores = num.einsum("bqhd,bkhd->bhqk", q, k) * att_mult
        mask = causal[None, None] & ok[:, None, None, :]
        probs = num.store(jax.nn.softmax(
            jnp.where(mask, scores, -jnp.inf), axis=-1))
        o = num.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, P, H * hd)
        return num.matmul(o, f32(a["wo"]))

    def mamba(h, j, ok):
        m = jax.tree.map(lambda w: w[j], mix["mamba"])
        d_in = m["norm_scale"].shape[-1]
        zxd = num.matmul(h, f32(m["w_in"]))
        z, xbc, dt = jnp.split(zxd, [d_in, zxd.shape[-1] - Hm], axis=-1)
        w, b = f32(m["conv_w"]), f32(m["conv_b"])
        A = -jnp.exp(m["a_log"])                            # (Hm,)
        dt = jax.nn.softplus(dt + m["dt_bias"])             # (B, P, Hm)
        dt = jnp.where(ok[..., None], dt, 0.0)   # a token it skipped
        hp = d_in // Hm

        def step(carry, inp):
            S, win = carry         # (B, Hm, hp, N), (B, K-1, conv)
            xbc_t, dt_t, ok_t = inp
            full = jnp.concatenate([win, xbc_t[:, None]], axis=1)
            c = jax.nn.silu(sum(full[:, i] * w[i] for i in range(K)) + b)
            xs, Bm, Cm = jnp.split(c, [d_in, d_in + N], axis=-1)
            xs = xs.reshape(B, Hm, hp)
            S = (jnp.exp(dt_t * A)[:, :, None, None] * S
                 + (dt_t[:, :, None] * xs)[..., None] * Bm[:, None, None, :])
            y = jnp.einsum("bhpn,bn->bhp", S, Cm, precision=HIGHEST) \
                + m["d_skip"][None, :, None] * xs
            # the window holds the last inputs this layer computed
            win = jnp.where(ok_t[:, None, None], full[:, 1:], win)
            return (S, win), y.reshape(B, d_in)

        S0 = jnp.zeros((B, Hm, hp, N), jnp.float32)
        win0 = jnp.zeros((B, K - 1, xbc.shape[-1]), jnp.float32)
        _, y = jax.lax.scan(step, (S0, win0), (
            jnp.moveaxis(xbc, 1, 0), jnp.moveaxis(dt, 1, 0), ok.T))
        y = num.store(jnp.moveaxis(y, 0, 1) * jax.nn.silu(z))
        return num.matmul(rms(y, m["norm_scale"]), f32(m["w_out"]))

    is_attn, slot = _tables(kinds)

    def layer(carry, inp):
        x, sel = carry
        lp, ok, j, attn_j, slot_j = inp                     # ok: (B, P)
        h = rms(x, lp["ln1"]["scale"])
        h = jax.lax.cond(attn_j, attention, mamba, h, slot_j, ok)
        x = num.store(x + res_mult * h)
        h = rms(x, lp["ln2"]["scale"])
        mp = lp["mlp"]
        g = num.matmul(h, f32(mp["wg"]))
        u = num.matmul(h, f32(mp["wi"]))
        x = num.store(x + res_mult * num.matmul(
            num.store(jax.nn.silu(g) * u), f32(mp["wo"])))
        ex = rms(x[:, out_pos], lp["exit_norm"]["scale"])
        sel = jnp.where((head_layer == j)[..., None], ex, sel)
        return (x, sel), None

    sel0 = jnp.zeros((B, out_pos.shape[0], D), jnp.float32)
    (x, sel), _ = jax.lax.scan(
        layer, (x, sel0), (params["layers"], jnp.moveaxis(valid, 1, 0),
                           jnp.arange(L), is_attn, slot))
    fin = rms(x[:, out_pos], params["final_norm"]["scale"])
    sel = jnp.where((head_layer == L)[..., None], fin, sel)
    return num.matmul(sel / logit_div, f32(params["exit_w"]))


def reference_logits(spec, params, tokens, valid, out_pos, head_layer,
                     num=ref_ops.REFERENCE, block: int = 2):
    """Logits (B, K, V) at ``out_pos`` (K,) of each sequence, each from
    the head ``head_layer`` (B, K) names: a layer's exit, or L for the
    final head. ``valid`` (B, L, P) marks the tokens each layer computed.
    Runs ``block`` sequences at a time."""
    k = dims(spec)
    static = (k["kinds"], k["H"], k["Hkv"], k["Hm"], k["N"], k["K"],
              float(spec["rms_norm_eps"]),
              float(spec["embedding_multiplier"]),
              float(spec["residual_multiplier"]),
              float(spec["attention_multiplier"]),
              float(spec["logits_scaling"]))
    with jax.default_matmul_precision("highest"):
        out = []
        for i in range(0, tokens.shape[0], block):
            out.append(jax.device_get(_forward(
                static, params, jnp.asarray(tokens[i:i + block]),
                jnp.asarray(valid[i:i + block]), jnp.asarray(out_pos),
                jnp.asarray(head_layer[i:i + block]), num)))
    return np.concatenate(out)


# ------------------------------------------------------------- operations

def attention_flops(spec, keys: int) -> float:
    """One attention layer's mixer for one token that reads ``keys`` keys:
    the q, k, v and o projections, scores and mix."""
    k = dims(spec)
    D, q, kv = k["D"], k["H"] * k["hd"], k["Hkv"] * k["hd"]
    return 2 * D * (q + 2 * kv) + 2 * q * D + 2 * 2 * q * keys


def mamba_flops(spec) -> float:
    """One Mamba2 mixer for one token: in_proj, the depthwise conv, the
    state update (decay, and dt x outer B added: 3 per state element), the
    read-out S C (2 per element) and out_proj."""
    k = dims(spec)
    D, d_in, state = k["D"], k["d_in"], k["Hm"] * k["P"] * k["N"]
    in_proj = 2 * D * (2 * d_in + 2 * k["G"] * k["N"] + k["Hm"])
    return (in_proj + 2 * k["K"] * k["conv"] + 3 * state + 2 * state
            + 2 * d_in * D)


def mlp_flops(spec) -> float:
    k = dims(spec)
    return 3 * 2 * k["D"] * k["F"]


def layer_flops(spec, layer: int, keys: int) -> float:
    """Operations of layer ``layer`` for one token that reads ``keys``
    keys (used only by an attention layer): its mixer and its MLP."""
    mixer = (attention_flops(spec, keys)
             if dims(spec)["kinds"][layer] == "attention"
             else mamba_flops(spec))
    return mixer + mlp_flops(spec)


def head_flops(spec) -> float:
    k = dims(spec)
    return 2 * k["D"] * k["V"]


def _layers_flops(spec, n: int, keys: int) -> float:
    """Layers 0..n-1 for one token that reads ``keys`` keys."""
    kinds = dims(spec)["kinds"][:n]
    a = kinds.count("attention")
    return (a * attention_flops(spec, keys) + (n - a) * mamba_flops(spec)
            + n * mlp_flops(spec))


def prefill_flops(spec, prompt_len: int) -> float:
    """One sequence's prefill: every layer over every prompt token (token
    p reads p + 1 keys at an attention layer) and the final head on the
    last token."""
    L = dims(spec)["L"]
    return sum(_layers_flops(spec, L, p + 1)
               for p in range(prompt_len)) + head_flops(spec)


def token_flops(spec, position: int, depth: int, offloaded: bool) -> float:
    """One decoded token at ``position``: layers 0..depth and one head; an
    offload adds the layers above depth and the final head. A token
    served at the last layer reads the final head once."""
    L = dims(spec)["L"]
    layers = L if offloaded else depth + 1
    heads = 2 if offloaded else 1
    return (_layers_flops(spec, layers, position + 1)
            + heads * head_flops(spec))
