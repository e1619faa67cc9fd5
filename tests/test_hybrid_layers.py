"""Typed hybrid layers (granite-4.0-h-micro): each layer's mixer is Mamba2
or attention by ``layer_types``, with weights and decode state held once
per type. A Mamba2 layer that a row skips passes the token by: the row's
SSM state and conv window stay bitwise as they were. The offload's wire
bytes price each layer by the state its own mixer keeps."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import mamba2 as m2
from repro.models import transformer as tf
from repro.models.api import build_model
from repro.serving.kvcache import (layer_state_bytes, offload_scale_vec,
                                   per_step_layer_bytes, step_slice_bytes)
from repro.serving.offload_codec import OffloadCodec

ARCH = "granite-4.0-h-micro"
TYPES = ("mamba", "attention", "mamba", "mamba")
B, S, TOTAL = 4, 5, 8


@pytest.fixture(scope="module")
def bed():
    cfg = dataclasses.replace(get_smoke_config(ARCH), dtype="float32",
                              num_layers=len(TYPES), layer_types=TYPES)
    params = build_model(cfg).init(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (B, S)).astype(np.int32)
    logits, caches = jax.jit(lambda p, t: tf.prefill(
        p, cfg, {"tokens": t}, cache_seq_len=TOTAL))(params, tokens)
    return cfg, params, caches, jnp.argmax(logits, -1).astype(jnp.int32)


def test_skipped_mamba_layer_keeps_row_state_bitwise(bed):
    """Edge then cloud: a row's Mamba layers above its split keep their
    SSM state and conv window bitwise (the cloud's inactive rows too); the
    layers a row runs move them."""
    cfg, params, caches, tok = bed
    depths = jnp.asarray([0, 1, 3, 2], jnp.int32)
    _, _, _, hidden, edge = jax.jit(lambda *a: tf.decode_step_masked(
        a[0], cfg, *a[1:], window_seq_len=TOTAL))(
        params, caches, tok, S, depths)
    mamba_layers = [i for i, t in enumerate(TYPES) if t == "mamba"]
    for key in ("ssm", "conv"):
        before = np.asarray(caches["ssm"][key])
        after = np.asarray(edge["ssm"][key])
        for j, layer in enumerate(mamba_layers):
            for b, d in enumerate(np.asarray(depths)):
                same = np.array_equal(after[j, b], before[j, b])
                assert same == (layer > d), (key, layer, b)
    # the cloud resumes row 1 only: row 0's skipped layers stay as they are
    active = jnp.asarray([False, True, False, False])
    _, cloud = jax.jit(lambda *a: tf.decode_step_resume(
        a[0], cfg, *a[1:], window_seq_len=TOTAL))(
        params, edge, hidden, S, depths, active)
    for key in ("ssm", "conv"):
        got = np.asarray(cloud["ssm"][key])
        want = np.asarray(edge["ssm"][key])
        np.testing.assert_array_equal(got[:, [0, 2, 3]], want[:, [0, 2, 3]])
        assert not np.array_equal(got[2, 1], want[2, 1])   # layer 3, row 1


def test_published_layer_bytes_follow_layer_types():
    """Per decode step a Mamba2 layer ships its whole SSM and conv state
    (64 heads x 64 x 128 float32, and 3 x 4352 float32 conv inputs), an
    attention layer one K/V slot (8 KV heads x 64, bfloat16, twice) and
    its 4-byte position."""
    cfg = get_config(ARCH)
    mamba = 64 * 64 * 128 * 4 + (m2.CONV_K - 1) * (4096 + 2 * 128) * 4
    assert mamba == 2_149_376
    attn = 2 * 8 * 64 * 2 + 4
    want = np.array([attn if t == "attention" else mamba
                     for t in cfg.layer_types])
    np.testing.assert_array_equal(per_step_layer_bytes(cfg), want)
    assert step_slice_bytes(cfg, 5) == 5 * mamba + attn
    # a loop over the layers carries each layer's whole state
    shapes = jax.eval_shape(lambda: tf.init_caches(cfg, 16, 640))
    whole = layer_state_bytes(cfg, shapes)
    np.testing.assert_array_equal(
        whole, np.where(want == attn, 16 * 640 * attn, 16 * mamba))


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "rwkv6-3b", "zamba2-1.2b",
                                  ARCH])
def test_offload_scale_vec_per_family(arch):
    """The codec touches only the hidden: each arm's ratio is
    (wire hidden + slice) / (raw hidden + slice) over the layers' own
    state, so it nears 1 as the slice grows."""
    cfg = get_config(arch)
    codec = OffloadCodec(quant="int8")
    cum = np.cumsum(per_step_layer_bytes(cfg)).astype(np.float64)
    raw = cfg.d_model * 2.0
    wire = codec.row_bytes(1, cfg.d_model, 2)
    vec = offload_scale_vec(cfg, codec)
    np.testing.assert_allclose(vec, (wire + cum) / (raw + cum))
    assert (np.diff(np.abs(vec - 1)) <= 0).all()


def test_smoke_variant_keeps_each_layer_type():
    cfg = get_smoke_config(ARCH)
    assert cfg.layer_kinds() == ("mamba", "attention")
    assert get_config(ARCH).layer_kinds().count("attention") == 4


def test_layer_types_must_cover_every_layer():
    cfg = dataclasses.replace(get_smoke_config(ARCH), num_layers=3)
    with pytest.raises(ValueError, match="layer_types"):
        tf.init_caches(cfg, 1, 4)
