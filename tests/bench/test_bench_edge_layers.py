"""``edge_layers_per_step.decode``: the layers the edge program's loop runs
per decode round, read from the program's counters. Reported by a traced
decode run, between 1 and the model's depth, and left out where the
program has no tracer or no layer counter."""
import os
import sys
import time
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402

from bench import harness  # noqa: E402

NAME = "edge_layers_per_step.decode"


def _ctx(telemetry):
    report = types.SimpleNamespace(telemetry=telemetry)
    return {"driver": types.SimpleNamespace(report=report)}


def test_traced_decode_run_reports_edge_layers():
    import jax

    cell = bench_testlib.tiny_cell("qwen3-1.7b-decode")
    cell.per_layer = [m for m in cell.per_layer if m["name"] == NAME]
    assert cell.per_layer, "the decode cell lists the metric"
    r = harness.run_cell(cell, seed=2 ** 31 + 7, seconds=0.5, trace=True,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1])
    assert r["correct"], r
    depth = cell.spec["num_hidden_layers"]
    v = r["metrics"][NAME]["value"]
    assert 1 <= v <= depth, v
    assert r["metrics"][NAME]["unit"] == "layers"


def test_reader_follows_its_definition():
    tel = {"spans": {}, "counts": {"splitee.decode.steps": 8,
                                   "splitee.decode.edge_layers": 20}}
    assert harness.metric_reader(NAME)(_ctx(tel)) == pytest.approx(2.5)


@pytest.mark.parametrize("ctx", [
    {"driver": types.SimpleNamespace()},                 # no report
    {"driver": types.SimpleNamespace(report=object())},  # no telemetry
    _ctx(None),
    _ctx({"spans": {}, "counts": {}}),
    _ctx({"spans": {}, "counts": {"splitee.decode.steps": 4}}),  # parent
])
def test_reader_finds_nothing_without_the_counter(ctx):
    assert harness.metric_reader(NAME)(ctx) is None
