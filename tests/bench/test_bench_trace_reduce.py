"""The reduction from a profiler trace to busy time, idle share, top
device ops and idle gaps labelled by the harness's host spans."""
import glob
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402,F401

from bench import trace_reduce as tr  # noqa: E402


def test_merge_unions_and_clips():
    got = tr.merge([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 25)
    assert got == [(1, 3), (5, 12), (20, 25)]


def test_gaps_are_the_complement():
    busy = [(1, 3), (5, 12)]
    assert tr.gaps(busy, 0, 15) == [(0, 1), (3, 5), (12, 15)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_gaps_labelled_by_innermost_span():
    spans = [(0, 100, "bench.window"), (10, 60, "bench.push"),
             (20, 30, "bench.edge_fn"), (70, 90, "bench.generator_wait")]
    idle = [(0, 10), (15, 25), (40, 50), (65, 95)]
    got = tr.label_gaps(idle, spans)
    assert got == pytest.approx({
        "host.other": 10 + 5 + 5, "bench.push": 5 + 10,
        "bench.edge_fn": 5, "bench.generator_wait": 20})


def test_reduce_averages_devices_and_ranks_ops():
    spans = [(0, 100, "bench.window"), (40, 100, "bench.tick")]
    device = {"/device:TPU:0": [(0, 30, "fusion"), (20, 40, "dot")],
              "/device:TPU:1": [(0, 20, "fusion"), (-50, 10, "copy")]}
    r = tr.reduce_events(device, spans)
    # busy 40 ns on device 0, 20 on device 1: 30 on average of 100
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["device_ops"][0] == ["fusion", pytest.approx(25e-9)]
    labels = dict((k, v) for k, v in r["idle_gaps"])
    assert labels["bench.tick"] == pytest.approx((60 + 60) / 2 * 1e-9)
    assert labels["host.other"] == pytest.approx((0 + 20) / 2 * 1e-9)


def test_reduction_of_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: the window and harness spans are
    found, the device work is found, and busy time lies inside the
    window."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.edge_fn"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.generator_wait"):
                    pass
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    r = tr.reduce_trace(str(tmp_path))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0.0 <= r["idle_share"] < 1.0
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    names = {k for k, _ in r["idle_gaps"]}
    assert names <= {"bench.edge_fn", "bench.generator_wait", "host.other"}


def test_op_names_drop_layouts():
    long = ("%fusion.143 = (f32[32,128]{1,0:T(8,128)S(1)}, "
            "f32[32,128,768]{2,1,0:T(8,128)S(1)}) fusion(" + "x" * 300)
    got = tr.op_name(long)
    assert got.startswith("fusion.143 = (f32[32,128], f32[32,128,768]) fusion(")
    assert len(got) == tr.OP_NAME_CHARS
