"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler refuses what the Pallas interpreter accepts: block
shapes that break the (8, 128) tiling, rank-1 blocks smaller than a
tile, a vmap over a ``pallas_call``. These tests compile the main path's
kernels at real widths for one chip of a ``v5e:2x2`` topology and check
that each program holds the kernel (``tpu_custom_call``). They run
nothing, so they say nothing about results or time.

The topology is described inside a fixture, never at import, and all of
these tests live in this one file: only one process may load the TPU
library, and every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.exit_confidence.ops import (exit_confidence,
                                               exit_confidence_fused)
from repro.kernels.flash_attention.ops import attention
from repro.kernels.wkv6.ops import wkv6

ELASTICBERT = dict(layers=12, d=768, heads=12, head_dim=64, seq=128,
                   classes=2)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip can be compiled for but not read back from, so
    # keep these compiles out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _plain(h, w):
    return exit_confidence(h, w, backend="pallas")


def _fused_layernorm(x, g, nb, w):
    return exit_confidence_fused(x, {"scale": g, "bias": nb}, w,
                                 kind="layernorm", backend="pallas")


@pytest.mark.parametrize("b", [1, 4, 32])
@pytest.mark.parametrize("fused", [False, True])
def test_exit_confidence_compiles_at_bucket_sizes(one_chip, b, fused):
    """Batches below the 8-row tile (the pow2 buckets 1, 2, 4) used to be
    refused: their rank-1 blocks were smaller than the tile."""
    d, v = ELASTICBERT["d"], ELASTICBERT["classes"]
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    if fused:
        _compiles_kernel(_fused_layernorm, s(b, d), s(d), s(d), s(d, v))
    else:
        _compiles_kernel(_plain, s(b, d), s(d, v))


@pytest.mark.parametrize("fused", [False, True])
def test_exit_confidence_compiles_for_stacked_heads(one_chip, fused):
    """All L per-layer exit heads in one launch (a vmap over the kernel
    was refused: it put the head axis in the block's sublane dim)."""
    n, d, v = ELASTICBERT["layers"], ELASTICBERT["d"], ELASTICBERT["classes"]
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    if fused:
        _compiles_kernel(_fused_layernorm, s(n, 5, d), s(n, d), s(n, d),
                         s(n, d, v))
    else:
        _compiles_kernel(_plain, s(n, 32, d), s(n, d, v))


def test_fused_exit_confidence_compiles_at_qwen3_vocab(one_chip):
    """V=151936 with D=2048: the head bias needs a 2-D block (a rank-1
    one got a layout Mosaic refuses)."""
    d, v = 2048, 151936

    def fused_rms(x, g, w, bias):
        return exit_confidence_fused(x, {"scale": g}, w, bias,
                                     kind="rmsnorm", backend="pallas")

    _compiles_kernel(fused_rms, _spec(one_chip, (8, d)),
                     _spec(one_chip, (d,)),
                     _spec(one_chip, (d, v), jnp.bfloat16),
                     _spec(one_chip, (v,)))


@pytest.mark.parametrize("fn", ["edge_fn_s", "edge_scan_fn"])
def test_all_exit_edge_programs_compile_at_elasticbert12(one_chip, fn):
    """The SplitEE-S and scan edge passes, whole, at 12 x 768 with the
    fused Pallas exit epilogue."""
    from repro.launch.serve import PUBLISHED_SEQ_LEN, testbed_config
    from repro.models.api import build_model
    from repro.serving import EdgeCloudRuntime

    cfg = testbed_config(published=True)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          shapes)
    rt = EdgeCloudRuntime(cfg, conf_backend="pallas", fused_exit=True)
    batch = {"tokens": _spec(one_chip, (32, PUBLISHED_SEQ_LEN), jnp.int32)}
    depth = (_spec(one_chip, (), jnp.int32) if fn == "edge_fn_s"
             else _spec(one_chip, (32,), jnp.int32))
    text = getattr(rt, fn).lower(params, batch, depth).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_elasticbert12(one_chip):
    e = ELASTICBERT
    q = _spec(one_chip, (32, e["heads"], e["seq"], e["head_dim"]))

    def attn(q, k, v):
        return attention(q, k, v, causal=False, backend="pallas")

    _compiles_kernel(attn, q, q, q)


def test_wkv6_compiles_at_rwkv6_3b(one_chip):
    """40 heads x 64: the per-head bonus ``u`` needs a block whose last
    two dims are the array's own."""
    from repro.configs import get_config
    cfg = get_config("rwkv6-3b")
    h, hd = cfg.num_heads, cfg.d_model // cfg.num_heads       # 40 x 64
    x = _spec(one_chip, (1, h, 256, hd))

    def run(r, k, v, w, u):
        return wkv6(r, k, v, w, u, backend="pallas")

    _compiles_kernel(run, x, x, x, x, _spec(one_chip, (h, hd)))
