"""The configurations' files: weights in the program's layout, the plain
reference against the program at a small size, and the operation counts
against a hand count."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import compare, driver, harness, ref_ops  # noqa: E402


def tiny(name, **over):
    cell = bench_testlib.tiny_cell(name)
    spec = dict(cell.spec, **over)
    return cell, spec


@pytest.mark.parametrize("cell_name", ["eb12-sst2-poisson",
                                       "qwen3-1.7b-decode"])
def test_weights_have_the_program_layout(cell_name):
    from repro.models.transformer import init_params
    cell, spec = tiny(cell_name)
    cfg = driver.program_config(spec)
    ours = cell.model.make_params(spec, 2 ** 33 + 5)
    theirs = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    again = cell.model.make_params(spec, 2 ** 33 + 5)
    other = cell.model.make_params(spec, 5)
    leaf = jax.tree.leaves(ours)[-1]
    assert np.array_equal(leaf, jax.tree.leaves(again)[-1])
    assert not np.array_equal(leaf, jax.tree.leaves(other)[-1])


def test_classify_reference_matches_the_program():
    from repro.serving import EdgeCloudRuntime
    cell, spec = tiny("eb12-sst2-poisson")
    cfg = driver.program_config(spec)
    params = cell.model.make_params(spec, 7)
    tokens = np.random.default_rng(0).integers(0, spec["vocab_size"],
                                               (6, 16)).astype(np.int32)
    ref_e, ref_f = cell.model.reference_logits(spec, params, tokens)
    rt = EdgeCloudRuntime(cfg)
    L = cfg.num_layers
    for d in range(L):
        conf, pred, hidden = rt.edge_fn(params, {"tokens": jnp.asarray(tokens)},
                                        jnp.int32(d))
        np.testing.assert_allclose(conf, compare.softmax_max(ref_e[:, d]),
                                   atol=2e-5)
        assert np.array_equal(pred, ref_e[:, d].argmax(-1))
        conf_l, pred_l = rt.cloud_fn(params, hidden, jnp.int32(d))
        np.testing.assert_allclose(conf_l, compare.softmax_max(ref_f),
                                   atol=2e-5)


def test_decode_reference_matches_served_tokens():
    """Served decode at float32 on the CPU, with exits and offloads at
    mixed depths, agrees with the reference's masked forward."""
    import time
    cell = bench_testlib.tiny_cell("qwen3-1.7b-decode")
    cell.spec = dict(cell.spec, torch_dtype="float32")
    cell.limits = {"served_logit_gap": 1e-3}
    r = harness.run_cell(cell, seed=11, seconds=0.3, trace=False,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1])
    assert r["correct"], r
    assert r["checks"]["served_logit_gap"]["value"] < 1e-3


def test_classify_flops_hand_count():
    cell, spec = tiny("eb12-sst2-poisson")
    m = cell.model
    S, D, F = 16, 64, 128
    per_layer = 2 * S * 4 * D * D + 2 * 2 * S * S * D + 2 * 2 * S * D * F
    assert m.layer_flops(spec, S) == per_layer
    assert m.sample_flops(spec, S, 0, False) == per_layer + 2 * D * 2
    assert m.sample_flops(spec, S, 1, True) == 3 * per_layer + 2 * (2 * D * 2)


def test_decode_flops_hand_count():
    cell, spec = tiny("qwen3-1.7b-decode")
    m = cell.model
    D, q, kv, F, V = 256, 256, 128, 512, 512
    layer = lambda keys: (2 * D * q + 2 * 2 * D * kv + 2 * q * D  # noqa
                          + 2 * 2 * q * keys + 3 * 2 * D * F)
    assert m.layer_flops(spec, 5) == layer(5)
    assert m.token_flops(spec, 9, 0, False) == layer(10) + 2 * D * V
    assert m.token_flops(spec, 9, 1, True) == 3 * layer(10) + 2 * 2 * D * V
    assert m.prefill_flops(spec, 3) == 3 * (layer(1) + layer(2) + layer(3)) \
        + 2 * D * V


def test_decode_layout_holes():
    prompt = np.arange(4)
    tokens, valid, out_pos, head, served = compare.decode_layout(
        prompt, 9, np.array([5, 6, 7]), np.array([0, 2, 1]),
        np.array([False, False, True]), num_layers=3)
    assert tokens.tolist() == [0, 1, 2, 3, 9, 5, 6]
    assert out_pos.tolist() == [3, 4, 5, 6]
    assert head.tolist() == [3, 0, 3, 3]    # depth 2 = last layer: final
    assert served.tolist() == [9, 5, 6, 7]
    assert valid[:, :4].all()
    assert valid[:, 4].tolist() == [True, False, False]
    assert valid[:, 5].all() and valid[:, 6].all()


def test_control_rounding():
    x = jnp.asarray([[1.0 + 2 ** -12, 3.0, -448.0, 1e-3]])
    assert ref_ops.round_to(x, "bf16")[0, 0] == 1.0
    q = ref_ops.round_to(x, "fp8")
    assert q[0, 2] == -448.0 and abs(q[0, 1] - 3.0) <= 0.25
    assert np.array_equal(ref_ops.round_to(x, "f32"), x)
