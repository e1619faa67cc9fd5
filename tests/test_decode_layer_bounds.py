"""The decode halves' layer loops run over a dynamic range: the edge
(``decode_step_masked``) stops at the step's deepest split, the cloud
(``decode_step_resume``) starts above the shallowest active split. Both
must return bitwise what a full sweep over every layer with per-row masks
returns: logits, every exit's confidence and prediction, the shipped
hidden and every cache leaf. The full sweep is the oracle in
``_decode_oracle``; ``decode_step`` runs the same bounded loop over every
layer with every row live, and is held to it too.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _decode_oracle import full_sweep, head, plain_step
from repro.configs import get_smoke_config
from repro.models import transformer as tf
from repro.models.api import build_model

# dense, ssm, hybrid with a shared block, hybrid with typed layers
ARCHS = ["qwen3-1.7b", "rwkv6-3b", "zamba2-1.2b", "granite-4.0-h-micro"]
L, B, S, TOTAL = 4, 4, 3, 6
# the typed hybrid's pattern at depth L: the mixed depths below exit at
# Mamba layers 0 and 1 and skip the attention layer 2 in some rows
TYPES = ("mamba", "mamba", "attention", "mamba")
# one case per family: dense, MoE, then the rest of ARCHS
FAMILIES = ["qwen3-1.7b", "mixtral-8x22b"] + ARCHS[1:]


def oracle_masked(params, cfg, caches, token, cur_index, depths):
    x = tf._embed_step(params, cfg, token)
    x, new_caches, pooled = full_sweep(
        params, cfg, caches, x, cur_index, lambda i: i <= depths,
        cfg.effective_window(TOTAL))
    conf, pred = tf.stacked_exit_confidence(params, cfg, pooled)
    return head(params, cfg, x), conf, pred, x, new_caches


def oracle_resume(params, cfg, caches, hidden, cur_index, depths, active):
    x, new_caches, _ = full_sweep(
        params, cfg, caches, hidden, cur_index,
        lambda i: active & (i > depths), cfg.effective_window(TOTAL))
    return head(params, cfg, x), new_caches


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
            "decode_step": jax.jit(lambda *a: build_model(cfg).decode_step(
                *a, all_exits=True, window_seq_len=TOTAL)),
            "oracle_step": jax.jit(lambda *a: plain_step(
                a[0], cfg, *a[1:], cfg.effective_window(TOTAL))),
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


@pytest.mark.parametrize("arch", FAMILIES)
def test_decode_step_matches_full_sweep(arch):
    """``Model.decode_step(all_exits=True)`` — the bounded loop over every
    layer, every row live — gives the oracle's full sweep bitwise: logits,
    every exit's confidence and prediction, and every cache leaf, in each
    family (dense, MoE, RWKV6, zamba2's shared block, typed layers)."""
    cfg, params, caches, tok, fns = _bed(arch)
    got = fns["decode_step"](params, caches, tok, S)
    _assert_same(got, fns["oracle_step"](params, caches, tok, S))
    assert got[1].shape == got[2].shape == (L, B)
