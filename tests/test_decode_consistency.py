"""Decode-path correctness: prefill + step-wise decode must reproduce the
full-sequence forward (per family), and the ring-buffer SWA cache must
equal full attention when the window covers the sequence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import f32_cfg
from repro.configs import get_smoke_config
from repro.models.api import build_model


def _lm_logits_full(model, params, tokens):
    """Final-layer next-token logits at the last position via prefill of
    the whole sequence."""
    logits, _ = model.prefill(params, {"tokens": tokens},
                              cache_seq_len=tokens.shape[1])
    return logits


@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen3-1.7b",
                                  "rwkv6-3b", "zamba2-1.2b",
                                  "mixtral-8x22b", "granite-4.0-h-micro"])
def test_prefill_then_decode_matches_full_forward(arch):
    cfg = f32_cfg(get_smoke_config(arch))
    if cfg.moe is not None:
        # drop-free capacity so prefill token-dropping (a legitimate
        # training-time behaviour) cannot perturb the equivalence check
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(
                cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)

    # path A: prefill S tokens, then decode token S
    _, caches = model.prefill(params, {"tokens": tokens[:, :S]},
                              cache_seq_len=S + 1)
    logits_a, _, _, _ = model.decode_step(
        params, caches, tokens[:, S], jnp.int32(S),
        split_layer=0, window_seq_len=S + 1)

    # path B: full forward over S+1 tokens
    logits_b = _lm_logits_full(model, params, tokens)

    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_buffer_window_cache_matches_full():
    """With window W < S the ring cache must attend to exactly the last W
    positions: compare against full-cache attention restricted by mask."""
    arch = "granite-3-2b"
    cfg = dataclasses.replace(f32_cfg(get_smoke_config(arch)),
                              sliding_window_override=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, W = 1, 20, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)

    # decode tokens one by one through the ring cache (window W)
    caches = model.init_caches(B, S)          # window-sized via override
    assert caches["attn"]["k"].shape[2] == W
    logits = None
    for t in range(S):
        logits, _, _, caches = model.decode_step(
            params, caches, tokens[:, t], jnp.int32(t),
            split_layer=0, window_seq_len=S)

    # reference: full prefill with the same sliding window
    ref_logits, _ = model.prefill(params, {"tokens": tokens},
                                  cache_seq_len=S)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_rwkv_stepwise_equals_prefill():
    cfg = f32_cfg(get_smoke_config("rwkv6-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 10
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    caches = model.init_caches(B, S)
    logits = None
    for t in range(S):
        logits, _, _, caches = model.decode_step(
            params, caches, tokens[:, t], jnp.int32(t), split_layer=0,
            window_seq_len=S)
    ref_logits, _ = model.prefill(params, {"tokens": tokens},
                                  cache_seq_len=S)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-4)
