"""Dry-run machinery unit tests that do NOT need 512 devices:
HLO collective parsing, depth-reduction, and input-spec construction for
every (arch x shape) combination (pure eval_shape)."""
import jax
import pytest

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.models.api import build_model

# importing the dry-run module must not force its 512 host devices: it
# sets XLA_FLAGS only when run as a script
from repro.launch.dryrun import _with_depth, parse_collective_bytes


FAKE_HLO = """
HloModule test
  %x = bf16[8,1024]{1,0} all-gather(%a), replica_groups={}
  %y = f32[16,16]{1,0} all-reduce(%b), to_apply=%sum
  %z = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%c, %d)
  %w = bf16[2,2]{1,0} reduce-scatter(%e)
  %p = f32[8]{0} collective-permute(%f)
  %n = f32[8,8]{1,0} add(%g, %h)
"""


def test_import_does_not_force_host_devices():
    import os
    assert "device_count=512" not in os.environ.get("XLA_FLAGS", "")


def test_parse_collective_bytes():
    out = parse_collective_bytes(FAKE_HLO)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 8 * 1024 * 2
    assert out["all-reduce"]["bytes"] == 16 * 16 * 4
    assert out["all-to-all"]["bytes"] == 2 * 4 * 4 * 4
    assert out["reduce-scatter"]["bytes"] == 2 * 2 * 2
    assert out["collective-permute"]["bytes"] == 8 * 4
    assert out["total_bytes"] == sum(
        out[k]["bytes"] for k in ("all-gather", "all-reduce", "all-to-all",
                                  "reduce-scatter", "collective-permute"))


def test_with_depth_scales_encoder_too():
    cfg = get_config("seamless-m4t-large-v2")
    r = _with_depth(cfg, 2)
    assert r.num_layers == 2 and r.encoder.num_layers == 2


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_construct_for_all_40_combos(arch, shape_name):
    """Every assigned (arch x shape) must produce coherent abstract specs
    — the cheap CPU proxy for the 512-device dry-run's input layer."""
    cfg = get_config(arch)
    model = build_model(cfg)
    shape = INPUT_SHAPES[shape_name]
    specs = model.input_specs(shape)
    leaves = jax.tree.leaves(specs)
    assert leaves, (arch, shape_name)
    for leaf in leaves:
        assert isinstance(leaf, jax.ShapeDtypeStruct)
        assert all(d > 0 for d in leaf.shape)
    if shape.kind == "decode":
        # decode caches must fit the pod: < 16 GB/chip x 256 chips global
        # (qwen1.5-32b MHA kv=40 decode_32k is the worst case: ~1.4 TB
        # global = 5.3 GB/device with the 8192 ring window)
        sizes = [leaf.size * leaf.dtype.itemsize for leaf in leaves]
        total = sum(sizes)
        assert total < 16e9 * 256 * 0.5, (arch, shape_name, total / 1e9)


def test_long500k_decode_caches_are_subquadratic():
    """No assigned arch may allocate a full 524288-deep dense KV cache ...
    except via ring-window or O(1) state (DESIGN §6 requirement)."""
    shape = INPUT_SHAPES["long_500k"]
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        model = build_model(cfg)
        specs = model.input_specs(shape)
        cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                          for leaf in jax.tree.leaves(specs["caches"]))
        # window 8192 / SSM state keeps caches small even stacked x layers
        assert cache_bytes < 60e9, (arch, cache_bytes / 1e9)
