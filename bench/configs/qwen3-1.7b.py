"""qwen3-1.7b: weights from the seed, the plain float32 reference of the
served tokens, and the useful operations of prefill and decode.

The block is Qwen3's: RMSNorm (eps 1e-6) before attention and MLP,
grouped-query attention with RMSNorm on each query and key head, rotary
positions on the two halves of the head dim (theta 1e6), SwiGLU MLP, no
biases. Each layer's early exit is an RMSNorm then the shared output
head; the final answer is the final RMSNorm then the same head.

Served decode leaves holes: a token that exits at layer d writes no key
or value above d, unless it was offloaded and the cloud completed the
remaining layers. The reference reproduces that by masking, at each
layer, the keys that layer never computed. Nothing here imports the
program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import ref_ops


def dims(spec):
    return dict(L=spec["num_hidden_layers"], D=spec["hidden_size"],
                H=spec["num_attention_heads"],
                Hkv=spec["num_key_value_heads"], hd=spec["head_dim"],
                F=spec["intermediate_size"], V=spec["vocab_size"])


def seed_key(seed: int):
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(spec, seed: int):
    """The weights, drawn on the device in one jitted call from ``seed``,
    in the program's parameter layout and served type (bfloat16)."""
    k = dims(spec)
    return _make(k["L"], k["D"], k["H"], k["Hkv"], k["hd"], k["F"], k["V"],
                 jnp.dtype(spec["torch_dtype"]), seed_key(seed))


@functools.partial(jax.jit, static_argnums=tuple(range(8)))
def _make(L, D, H, Hkv, hd, F, V, dtype, key):
    ks = iter(jax.random.split(key, 16))

    def dense(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    layers = {
        "ln1": {"scale": scale((L, D))},
        "attn": {"wq": dense((L, D, H * hd)), "wk": dense((L, D, Hkv * hd)),
                 "wv": dense((L, D, Hkv * hd)), "wo": dense((L, H * hd, D)),
                 "q_norm": scale((L, hd)), "k_norm": scale((L, hd))},
        "ln2": {"scale": scale((L, D))},
        "mlp": {"wi": dense((L, D, F)), "wg": dense((L, D, F)),
                "wo": dense((L, F, D))},
        "exit_norm": {"scale": scale((L, D))},
    }
    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, D), jnp.float32)
                  ).astype(dtype),
        "layers": layers,
        "final_norm": {"scale": scale((D,))},
        "exit_w": dense((D, V)),
    }


@functools.partial(jax.jit, static_argnums=(0, 6))
def _forward(static, params, tokens, valid, out_pos, head_layer,
             num: ref_ops.Numerics):
    H, Hkv, hd, eps, theta = static
    f32 = lambda a: num.store(a.astype(jnp.float32))  # noqa: E731
    x = f32(jnp.take(params["embed"], tokens, axis=0))
    B, P, D = x.shape
    L = valid.shape[1]
    pos = jnp.arange(P)
    causal = pos[None, :] <= pos[:, None]                   # (q, k)

    def rms(v, s):
        return num.store(ref_ops.rmsnorm(v, f32(s), eps))

    def layer(carry, inp):
        x, sel = carry
        lp, ok, j = inp                                     # ok: (B, P)
        a = lp["attn"]
        h = rms(x, lp["ln1"]["scale"])
        q = num.matmul(h, f32(a["wq"])).reshape(B, P, H, hd)
        k = num.matmul(h, f32(a["wk"])).reshape(B, P, Hkv, hd)
        v = num.matmul(h, f32(a["wv"])).reshape(B, P, Hkv, hd)
        q = num.store(ref_ops.rope(rms(q, a["q_norm"]), pos, theta))
        k = num.store(ref_ops.rope(rms(k, a["k_norm"]), pos, theta))
        mask = causal[None, None] & ok[:, None, None, :]
        o = ref_ops.attention(num, q, k, v, mask).reshape(B, P, H * hd)
        x = num.store(x + num.matmul(o, f32(a["wo"])))
        h = rms(x, lp["ln2"]["scale"])
        m = lp["mlp"]
        g = num.matmul(h, f32(m["wg"]))
        u = num.matmul(h, f32(m["wi"]))
        x = num.store(x + num.matmul(num.store(jax.nn.silu(g) * u),
                                     f32(m["wo"])))
        ex = rms(x[:, out_pos], lp["exit_norm"]["scale"])
        sel = jnp.where((head_layer == j)[..., None], ex, sel)
        return (x, sel), None

    sel0 = jnp.zeros((B, out_pos.shape[0], D), jnp.float32)
    (x, sel), _ = jax.lax.scan(
        layer, (x, sel0),
        (params["layers"], jnp.moveaxis(valid, 1, 0), jnp.arange(L)))
    fin = rms(x[:, out_pos], params["final_norm"]["scale"])
    sel = jnp.where((head_layer == L)[..., None], fin, sel)
    return num.matmul(sel, f32(params["exit_w"]))


def reference_logits(spec, params, tokens, valid, out_pos, head_layer,
                     num=ref_ops.REFERENCE, block: int = 2):
    """Logits (B, K, V) at ``out_pos`` (K,) of each sequence, each from
    the head ``head_layer`` (B, K) names: a layer's exit, or L for the
    final head. ``valid`` (B, L, P) marks the keys each layer computed.
    Runs ``block`` sequences at a time."""
    static = (spec["num_attention_heads"], spec["num_key_value_heads"],
              spec["head_dim"], float(spec["rms_norm_eps"]),
              float(spec["rope_theta"]))
    out = []
    for i in range(0, tokens.shape[0], block):
        out.append(jax.device_get(_forward(
            static, params, jnp.asarray(tokens[i:i + block]),
            jnp.asarray(valid[i:i + block]), jnp.asarray(out_pos),
            jnp.asarray(head_layer[i:i + block]), num)))
    return np.concatenate(out)


def layer_flops(spec, keys: int) -> float:
    """Operations of one layer for one token that reads ``keys`` keys:
    the q, k, v and o projections, scores and mix, and the SwiGLU MLP."""
    k = dims(spec)
    D, q, kv, F = k["D"], k["H"] * k["hd"], k["Hkv"] * k["hd"], k["F"]
    return (2 * D * (q + 2 * kv) + 2 * q * D + 2 * 2 * q * keys
            + 3 * 2 * D * F)


def head_flops(spec) -> float:
    k = dims(spec)
    return 2 * k["D"] * k["V"]


def prefill_flops(spec, prompt_len: int) -> float:
    """One sequence's prefill: every layer over every prompt token (token
    p reads p + 1 keys) and the final head on the last token."""
    L = dims(spec)["L"]
    per_layer = sum(layer_flops(spec, p + 1) for p in range(prompt_len))
    return L * per_layer + head_flops(spec)


def token_flops(spec, position: int, depth: int, offloaded: bool) -> float:
    """One decoded token at ``position``: layers 0..depth and one head;
    an offload adds the layers above depth and the final head. A token
    served at the last layer reads the final head once."""
    L = dims(spec)["L"]
    layers = L if offloaded else depth + 1
    heads = 2 if offloaded else 1
    return layers * layer_flops(spec, position + 1) + heads * head_flops(spec)
