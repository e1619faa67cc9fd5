"""The decode halves' layer loops run over a dynamic range: the edge
(``decode_step_masked``) stops at the step's deepest split, the cloud
(``decode_step_resume``) starts above the shallowest active split. Both
must return bitwise what a full sweep over every layer with per-row masks
returns: logits, every exit's confidence and prediction, the shipped
hidden and every cache leaf. The full sweep is kept here as the oracle.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import attention as attn
from repro.models import mlp as ff
from repro.models import transformer as tf
from repro.models.api import build_model
from repro.models.common import apply_norm
from repro.sharding import constrain

# dense, ssm, hybrid with a shared block, hybrid with typed layers
ARCHS = ["qwen3-1.7b", "rwkv6-3b", "zamba2-1.2b", "granite-4.0-h-micro"]
L, B, S, TOTAL = 4, 4, 3, 6
# the typed hybrid's pattern at depth L: the mixed depths below exit at
# Mamba layers 0 and 1 and skip the attention layer 2 in some rows
TYPES = ("mamba", "mamba", "attention", "mamba")


def _shared_attn(cfg, params, xx2, occ, i, m, cur_index, window):
    """The hybrid family's shared attention block after every k-th layer,
    its cache advanced for the rows in ``m`` only."""
    k = cfg.hybrid_attn_every
    sp = params["shared_attn"]

    def with_attn(args):
        xx2, occ = args
        oi = (i + 1) // k - 1
        sl = jax.tree.map(lambda a: a[oi], occ)
        h, new_sl = attn.attn_decode(
            sp["attn"], apply_norm(xx2, sp["ln1"], cfg.norm), sl,
            cur_index, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, window=window,
            rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm)
        xx2 = xx2 + h
        xx2 = xx2 + ff.mlp_forward(
            sp["mlp"], apply_norm(xx2, sp["ln2"], cfg.norm), cfg.activation)
        new_sl = tf._mask_rows(m, new_sl, sl)
        occ = jax.tree.map(
            lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                buf, ns, oi, 0), occ, new_sl)
        return xx2, occ

    return jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0), with_attn,
                        lambda a: a, (xx2, occ))


def _full_sweep(params, cfg, caches, x, cur_index, live, window):
    """Every layer, rows outside ``live(i)`` masked: (x, caches, pooled)."""
    hybrid = cfg.family == "hybrid" and not cfg.layer_types

    def body(carry, inp):
        xx, occ = carry
        lp, st, i = inp
        m = live(i)
        xx2, new_st = tf._layer_decode(cfg, params, lp, xx, st, cur_index,
                                       window=window)
        if hybrid:
            xx2, occ = _shared_attn(cfg, params, xx2, occ, i, m, cur_index,
                                    window)
        xx = jnp.where(m[:, None, None], xx2, xx)
        new_st = tf._mask_rows(m, new_st, st)
        pooled = tf.pool_hidden(cfg, tf._head_norm(cfg, xx,
                                                   lp["exit_norm"]))
        return (xx, occ), (new_st, pooled)

    occ = caches["attn"] if hybrid else None
    (x, occ), new_caches, pooled = tf._scan_layers(cfg, params, body,
                                                   (x, occ), caches)
    if hybrid:
        new_caches["attn"] = occ
    return x, new_caches, pooled


def _head(params, cfg, x):
    ews = tf._exit_heads(params, cfg)
    ew = ews if ews.ndim == 2 else ews[-1]
    xf = tf._head_norm(cfg, x, params["final_norm"])
    return constrain(xf[:, -1, :] @ ew, "batch", "model")


def oracle_masked(params, cfg, caches, token, cur_index, depths):
    x = tf._embed_step(params, cfg, token)
    x, new_caches, pooled = _full_sweep(
        params, cfg, caches, x, cur_index, lambda i: i <= depths,
        cfg.effective_window(TOTAL))
    conf, pred = tf.stacked_exit_confidence(params, cfg, pooled)
    return _head(params, cfg, x), conf, pred, x, new_caches


def oracle_resume(params, cfg, caches, hidden, cur_index, depths, active):
    x, new_caches, _ = _full_sweep(
        params, cfg, caches, hidden, cur_index,
        lambda i: active & (i > depths), cfg.effective_window(TOTAL))
    return _head(params, cfg, x), new_caches


_BEDS = {}


def _bed(arch):
    """(cfg, params, prefilled caches, first token) at float32, L layers."""
    if arch not in _BEDS:
        cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32",
                                  num_layers=L)
        if cfg.layer_types:
            cfg = dataclasses.replace(cfg, layer_types=TYPES)
        params = build_model(cfg).init(jax.random.PRNGKey(1))
        prompts = np.random.default_rng(5).integers(
            0, cfg.vocab_size, size=(B, S)).astype(np.int32)
        logits0, caches = jax.jit(
            lambda p, t: tf.prefill(p, cfg, {"tokens": t},
                                    cache_seq_len=TOTAL))(params, prompts)
        tok = jnp.argmax(logits0, -1).astype(jnp.int32)
        fns = {
            "masked": jax.jit(lambda *a: tf.decode_step_masked(
                a[0], cfg, *a[1:], window_seq_len=TOTAL)),
            "resume": jax.jit(lambda *a: tf.decode_step_resume(
                a[0], cfg, *a[1:], window_seq_len=TOTAL)),
            "oracle_masked": jax.jit(
                lambda *a: oracle_masked(a[0], cfg, *a[1:])),
            "oracle_resume": jax.jit(
                lambda *a: oracle_resume(a[0], cfg, *a[1:])),
        }
        _BEDS[arch] = (cfg, params, caches, tok, fns)
    return _BEDS[arch]


def _assert_same(got, want):
    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(gl) == len(wl)
    for g, w in zip(gl, wl):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


DEPTHS = {"all_0": [0] * B, "all_last": [L - 1] * B,
          "mixed": [1, 2, 0, 1]}          # deepest split below L-1
ACTIVE = {"none": [False] * B, "one_row": [False, True, False, False],
          "all_rows": [True] * B}


@pytest.mark.parametrize("active", list(ACTIVE))
@pytest.mark.parametrize("depths", list(DEPTHS))
@pytest.mark.parametrize("arch", ARCHS)
def test_layer_range_matches_full_masked_sweep(arch, depths, active):
    """Edge then cloud at one decode position: the bounded loops give the
    full masked sweep's outputs bitwise, in each family, for uniform and
    mixed depths and for empty, single-row and full active sets."""
    cfg, params, caches, tok, fns = _bed(arch)
    d = jnp.asarray(DEPTHS[depths], jnp.int32)
    act = jnp.asarray(ACTIVE[active])
    edge = fns["masked"](params, caches, tok, S, d)
    _assert_same(edge, fns["oracle_masked"](params, caches, tok, S, d))
    _, conf, pred, hidden, edge_caches = edge
    assert conf.shape == pred.shape == (L, B)
    cloud = fns["resume"](params, edge_caches, hidden, S, d, act)
    _assert_same(cloud, fns["oracle_resume"](params, edge_caches, hidden, S,
                                             d, act))
    if active == "none":
        _assert_same(cloud[1], edge_caches)
