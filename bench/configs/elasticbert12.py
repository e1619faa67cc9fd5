"""elasticbert12: weights from the seed, the plain reference of the
served classifier, and the useful operations of the served work.

The block is the program's (see ``departures`` in elasticbert12.json):
pre-LN, bidirectional attention with rotary positions, no biases, a
LayerNorm + (D, C) exit head on the first token after every layer, and
the last layer's head behind the final LayerNorm for the cloud's answer.
Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import ref_ops


def dims(spec):
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    return dict(L=spec["num_hidden_layers"], D=d, H=h, hd=d // h,
                F=spec["intermediate_size"], V=spec["vocab_size"],
                C=spec["num_labels"])


def make_params(spec, seed: int):
    """The weights, drawn on the device in one jitted call from ``seed``,
    in the program's parameter layout and served type (float32)."""
    k = dims(spec)
    return _make(k["L"], k["D"], k["F"], k["V"], k["C"],
                 jnp.dtype(spec["torch_dtype"]), seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    is folded in, not truncated)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _make(L, D, F, V, C, dtype, key):
    ks = iter(jax.random.split(key, 16))

    def dense(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    def norm(shape):
        return {"scale": (1.0 + 0.1 * jax.random.normal(
                    next(ks), shape, jnp.float32)).astype(dtype),
                "bias": (0.1 * jax.random.normal(
                    next(ks), shape, jnp.float32)).astype(dtype)}

    layers = {
        "ln1": norm((L, D)),
        "attn": {"wq": dense((L, D, D)), "wk": dense((L, D, D)),
                 "wv": dense((L, D, D)), "wo": dense((L, D, D))},
        "ln2": norm((L, D)),
        "mlp": {"wi": dense((L, D, F)), "wo": dense((L, F, D))},
        "exit_norm": norm((L, D)),
        "exit_w": dense((L, D, C)),
    }
    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, D), jnp.float32)
                  ).astype(dtype),
        "layers": layers,
        "final_norm": norm((D,)),
    }


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(static, params, tokens, num: ref_ops.Numerics):
    """Every exit's logits (B, L, C) and the final head's (B, C)."""
    H, eps, theta = static
    f32 = lambda a: num.store(a.astype(jnp.float32))  # noqa: E731
    x = f32(jnp.take(params["embed"], tokens, axis=0))
    B, P, D = x.shape
    hd = D // H
    pos = jnp.arange(P)
    mask = jnp.ones((1, 1, P, P), bool)

    def ln(v, p):
        return num.store(ref_ops.layernorm(v, f32(p["scale"]),
                                           f32(p["bias"]), eps))

    def layer(x, lp):
        h = ln(x, lp["ln1"])
        a = lp["attn"]
        q = num.matmul(h, f32(a["wq"])).reshape(B, P, H, hd)
        k = num.matmul(h, f32(a["wk"])).reshape(B, P, H, hd)
        v = num.matmul(h, f32(a["wv"])).reshape(B, P, H, hd)
        q = num.store(ref_ops.rope(q, pos, theta))
        k = num.store(ref_ops.rope(k, pos, theta))
        o = ref_ops.attention(num, q, k, v, mask).reshape(B, P, D)
        x = num.store(x + num.matmul(o, f32(a["wo"])))
        h = ln(x, lp["ln2"])
        m = num.store(ref_ops.gelu_tanh(num.matmul(h, f32(lp["mlp"]["wi"]))))
        x = num.store(x + num.matmul(m, f32(lp["mlp"]["wo"])))
        ex = num.matmul(ln(x[:, 0], lp["exit_norm"]), f32(lp["exit_w"]))
        return x, ex

    x, exits = jax.lax.scan(layer, x, params["layers"])
    last = jax.tree.map(lambda a: a[-1], params["layers"])
    final = num.matmul(ln(x[:, 0], params["final_norm"]),
                       f32(last["exit_w"]))
    return jnp.moveaxis(exits, 0, 1), final


def reference_logits(spec, params, tokens, num=ref_ops.REFERENCE,
                     block: int = 64):
    """(exit logits (B, L, C), final logits (B, C)), float32, with the
    rounding ``num`` states, computed ``block`` rows at a time so the
    reference fits beside the program."""
    static = (spec["num_attention_heads"], float(spec["layer_norm_eps"]),
              float(spec["rope_theta"]))
    exits, finals = [], []
    for i in range(0, tokens.shape[0], block):
        e, f = _forward(static, params, jnp.asarray(tokens[i:i + block]),
                        num)
        exits.append(jax.device_get(e))
        finals.append(jax.device_get(f))
    import numpy as np
    return np.concatenate(exits), np.concatenate(finals)


def layer_flops(spec, seq_len: int) -> float:
    """Operations of one layer over one ``seq_len``-token sample:
    four D x D projections, scores and mix, and the two MLP products."""
    k = dims(spec)
    S, D, F = seq_len, k["D"], k["F"]
    return 2 * S * 4 * D * D + 2 * 2 * S * S * D + 2 * 2 * S * D * F


def head_flops(spec) -> float:
    k = dims(spec)
    return 2 * k["D"] * k["C"]


def sample_flops(spec, seq_len: int, arm: int, offloaded: bool) -> float:
    """Useful operations of one served sample: layers 1..arm+1 and that
    exit's head; an offload adds the remaining layers and the final head."""
    L = dims(spec)["L"]
    layers = L if offloaded else arm + 1
    heads = 2 if offloaded else 1
    return layers * layer_flops(spec, seq_len) + heads * head_flops(spec)
