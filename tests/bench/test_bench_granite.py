"""granite-4.0-h-micro's files: the configuration as published, weights in
the program's layout, the plain reference against the served decode at a
small size (exits and offloads at Mamba layers, so the reference's holes
in the recurrence are exercised), the control, the operation counts by
layer type, and the two per-layer readers of its cell."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402

import jax  # noqa: E402

from bench import driver, harness  # noqa: E402

CELL = "granite-4.0-h-micro-decode"
# every width cut, the 40 layers and their published pattern kept (the
# program takes the pattern from its registry), the state size kept
TINY_SPEC = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 2, "intermediate_size": 128,
             "vocab_size": 512, "mamba_n_heads": 2,
             "torch_dtype": "float32"}
TINY_TRAFFIC = {"batch_size": 2, "prompt_len": 8, "new_tokens": 6,
                "alpha": {"probe_steps": 4}, "compare": {"sequences": 32}}
# float32 on the CPU: the program and the reference differ only in the
# order of their sums (the program's chunked scan at prefill against the
# reference's token-by-token recurrence), far below a gap that moves an
# argmax; the served tokens read 0.0 on three seeds and the control 0.16
# to 0.22, so 1e-3 leaves room for a near tie and none for the control
LIMIT = 1e-3


def tiny_cell():
    cell = harness.load_cell(CELL)
    cell.spec = bench_testlib.merge(cell.spec, TINY_SPEC)
    cell.traffic = bench_testlib.merge(cell.traffic, TINY_TRAFFIC)
    cell.limits = {"served_logit_gap": LIMIT}
    return cell


@pytest.fixture(scope="module")
def served():
    """One tiny window through the cell's driver, then the comparison with
    the reference and with the control in the program's place."""
    cell = tiny_cell()
    drv = harness.driver_class(cell.traffic["kind"])(
        cell, seed=2 ** 33 + 11, seconds=1.0, devices=jax.devices()[:1],
        probe=harness.Probe(annotate=False))
    drv.setup()
    window = drv.window(1.0)
    checks = drv.check(control=True)
    return cell, drv, window, checks


def test_config_file_holds_the_registry_numbers():
    from repro.configs import get_config
    from repro.models import mamba2 as m2
    spec = harness.load_cell(CELL).spec
    cfg = get_config(spec["program_arch"])
    assert driver.program_config(spec) == cfg
    assert tuple(spec["layer_types"]) == cfg.layer_types
    assert spec["rms_norm_eps"] == cfg.norm_eps
    for key in ("embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling"):
        assert spec[key] == getattr(cfg, key), key
    assert spec["position_embedding_type"] == cfg.position_embedding
    assert spec["mamba_d_state"] == cfg.ssm.state_size
    assert spec["mamba_chunk_size"] == cfg.ssm.chunk_size
    assert spec["mamba_expand"] == cfg.ssm.expand
    assert spec["mamba_n_heads"] * spec["mamba_d_head"] == \
        cfg.ssm.expand * cfg.d_model
    assert spec["mamba_d_head"] == m2.HEAD_DIM
    assert spec["mamba_d_conv"] == m2.CONV_K
    assert spec["mamba_n_groups"] == 1
    assert spec["mamba_conv_bias"] and not spec["mamba_proj_bias"]
    assert spec["shared_intermediate_size"] == cfg.d_ff
    assert spec["num_local_experts"] == 0 and cfg.moe is None


def test_weights_have_the_program_layout():
    from repro.models.transformer import init_params
    cell = tiny_cell()
    spec = cell.spec
    cfg = driver.program_config(spec)
    ours = cell.model.make_params(spec, 2 ** 33 + 5)
    theirs = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    again = cell.model.make_params(spec, 2 ** 33 + 5)
    other = cell.model.make_params(spec, 5)
    leaf = ours["mixers"]["mamba"]["w_in"]
    assert np.array_equal(leaf, again["mixers"]["mamba"]["w_in"])
    assert not np.array_equal(leaf, other["mixers"]["mamba"]["w_in"])


def test_served_decode_matches_the_reference(served):
    """Exits and offloads at Mamba layers, and the served tokens within
    the limit of the reference's best at the head that served each."""
    cell, drv, window, checks = served
    assert window["failed"] == 0 and window["attempted"] > 0
    dec = drv.report.decode
    kinds = np.asarray(cell.spec["layer_types"])
    depths = np.asarray(dec["realized_depths"])
    offl = np.asarray(dec["offloaded_steps"], bool)
    shallow = depths < len(kinds) - 1
    assert (shallow & ~offl & (kinds[depths] == "mamba")).any()
    assert (offl & (kinds[depths] == "mamba")).any()
    assert harness.verdict(checks, cell.limits, 0), checks
    assert checks["served_logit_gap"] < LIMIT


def test_control_is_not_correct(served):
    """The reference in fp8 products and bf16 stores, in the program's
    place, fails the limit the program meets."""
    cell, _, _, checks = served
    assert checks["control"]["served_logit_gap"] > LIMIT
    assert not harness.verdict(checks["control"], cell.limits, 0)


def test_new_readers_read_the_program_counters(served):
    cell, drv, _, _ = served
    counts = drv.report.telemetry["counts"]
    ctx = {"driver": drv}
    state = harness.metric_reader("state_mib_per_step.decode")(ctx)
    wire = harness.metric_reader("offload_mib_per_launch.decode")(ctx)
    assert state == counts["splitee.decode.state_bytes"] \
        / counts["splitee.decode.steps"] / 2 ** 20 > 0
    assert wire == counts["splitee.decode.offload_bytes"] \
        / counts["splitee.decode.cloud_launches"] / 2 ** 20 > 0


@pytest.mark.parametrize("name", ["state_mib_per_step.decode",
                                  "offload_mib_per_launch.decode"])
def test_new_readers_find_nothing_without_counters(name):
    read = harness.metric_reader(name)

    class Report:
        telemetry = {"spans": {}, "counts": {"splitee.decode.steps": 3}}

    assert read({"driver": object()}) is None
    assert read({"driver": type("D", (), {"report": None})()}) is None
    assert read({"driver": type("D", (), {"report": Report()})()}) is None


def test_decode_flops_hand_count():
    """Layer 0 is a Mamba2 layer and layer 5 the first attention layer."""
    m = tiny_cell().model
    spec = tiny_cell().spec
    D, F, V, H, kv = 64, 128, 512, 4, 2
    hd, Hm, P, N, K = 16, 2, 64, 128, 4
    d_in, conv = Hm * P, Hm * P + 2 * N
    mlp = 3 * 2 * D * F
    mamba = (2 * D * (2 * d_in + 2 * N + Hm) + 2 * K * conv
             + 5 * Hm * P * N + 2 * d_in * D)
    attn = lambda keys: (2 * D * (H * hd + 2 * kv * hd)  # noqa: E731
                         + 2 * H * hd * D + 2 * 2 * H * hd * keys)
    head = 2 * D * V
    assert m.layer_flops(spec, 0, 7) == mamba + mlp
    assert m.layer_flops(spec, 5, 7) == attn(7) + mlp
    assert m.token_flops(spec, 9, 0, False) == mamba + mlp + head
    assert m.token_flops(spec, 9, 5, False) == \
        5 * mamba + attn(10) + 6 * mlp + head
    assert m.token_flops(spec, 9, 2, True) == \
        36 * mamba + 4 * attn(10) + 40 * mlp + 2 * head
    assert m.prefill_flops(spec, 3) == \
        3 * (36 * mamba + 40 * mlp) + 4 * (attn(1) + attn(2) + attn(3)) \
        + head


def test_cell_reports_the_new_metrics():
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"state_mib_per_step.decode",
            "offload_mib_per_launch.decode"} <= names
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "token_gap_p95_ms", "setup_s"}
    assert driver.program_config(cell.spec).layer_types == tuple(
        cell.spec["layer_types"])
