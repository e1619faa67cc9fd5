"""The per-layer metrics that read the program's own spans and counters
(``ServeReport.telemetry``): reported by a traced decode run, computed
from the totals as defined, and left out where the program has no
tracer."""
import math
import os
import sys
import time
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402

from bench import harness  # noqa: E402

PROGRAM_METRICS = ("host_ms_per_step.decode", "cloud_resume_ms.decode",
                   "fold_ms_per_step.decode")


def _ctx(telemetry):
    report = types.SimpleNamespace(telemetry=telemetry)
    return {"driver": types.SimpleNamespace(report=report)}


def _spans(**totals):
    return {f"splitee.decode.{k}": {"n": n, "total_ms": ms, "self_ms": ms}
            for k, (n, ms) in totals.items()}


def test_traced_decode_run_reports_the_program_metrics():
    import jax

    cell = bench_testlib.tiny_cell("qwen3-1.7b-decode")
    # mfu.decode needs the chip's peak, which the table rightly lacks for
    # the CPU; every other per-layer metric of the cell is read
    cell.per_layer = [m for m in cell.per_layer if m["name"] != "mfu.decode"]
    r = harness.run_cell(cell, seed=2 ** 31 + 5, seconds=0.5, trace=True,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1])
    assert r["correct"], r
    for name in PROGRAM_METRICS:
        v = r["metrics"][name]["value"]
        assert math.isfinite(v) and v >= 0, (name, v)
        assert r["metrics"][name]["unit"] == "ms"
    # the accepted per-layer metrics are still there beside them
    assert {"offload_share.decode", "idle_share.decode"} <= set(r["metrics"])


def test_readers_follow_their_definitions():
    tel = {"spans": _spans(step=(10, 200.0), edge_wait=(10, 80.0),
                           cloud_wait=(4, 20.0), codec=(4, 6.0),
                           cloud=(4, 2.0), fold=(10, 15.0)),
           "counts": {"splitee.decode.steps": 10,
                      "splitee.decode.cloud_launches": 4}}
    read = {m: harness.metric_reader(m) for m in PROGRAM_METRICS}
    assert read["host_ms_per_step.decode"](_ctx(tel)) == pytest.approx(
        (200.0 - 80.0 - 20.0) / 10)
    assert read["cloud_resume_ms.decode"](_ctx(tel)) == pytest.approx(
        (6.0 + 2.0 + 20.0) / 4)
    assert read["fold_ms_per_step.decode"](_ctx(tel)) == pytest.approx(
        15.0 / 10)


@pytest.mark.parametrize("ctx", [
    {"driver": types.SimpleNamespace()},                 # no report
    {"driver": types.SimpleNamespace(report=object())},  # no telemetry
    _ctx(None),
    _ctx({"spans": {}, "counts": {}}),
])
def test_readers_find_nothing_without_a_tracer(ctx):
    for name in PROGRAM_METRICS:
        assert harness.metric_reader(name)(ctx) is None


def test_no_offload_leaves_cloud_resume_out():
    tel = {"spans": _spans(step=(3, 9.0), edge_wait=(3, 3.0),
                           fold=(3, 1.0)),
           "counts": {"splitee.decode.steps": 3}}
    assert harness.metric_reader("cloud_resume_ms.decode")(_ctx(tel)) is None
    assert harness.metric_reader("host_ms_per_step.decode")(
        _ctx(tel)) == pytest.approx(2.0)
