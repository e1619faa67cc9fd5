"""``host_fetches_per_step.decode``: the program's blocking fetches of
device results per token round, reported by a traced decode run and left
out where the program does not count its fetches."""
import os
import sys
import time
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402

from bench import harness  # noqa: E402

NAME = "host_fetches_per_step.decode"


def _ctx(telemetry):
    report = types.SimpleNamespace(telemetry=telemetry)
    return {"driver": types.SimpleNamespace(report=report)}


def test_traced_decode_run_reports_one_fetch_per_program_run():
    """Without a codec a round fetches once after the edge and once after
    each cloud launch: one fetch a step plus the share of steps that
    launched the cloud."""
    import jax

    cell = bench_testlib.tiny_cell("qwen3-1.7b-decode")
    # mfu.decode needs the chip's peak, which the table rightly lacks for
    # the CPU
    cell.per_layer = [m for m in cell.per_layer if m["name"] != "mfu.decode"]
    assert NAME in {m["name"] for m in cell.per_layer}
    r = harness.run_cell(cell, seed=2 ** 31 + 7, seconds=0.5, trace=True,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1])
    assert r["correct"], r
    m = r["metrics"][NAME]
    assert m["unit"] == "fetches"
    share = r["metrics"]["offload_share.decode"]["value"] / 100.0
    assert m["value"] == pytest.approx(1.0 + share)
    assert 1.0 <= m["value"] <= 2.0


def test_reader_follows_its_definition():
    tel = {"spans": {}, "counts": {"splitee.decode.steps": 10,
                                   "splitee.decode.host_fetches": 17}}
    assert harness.metric_reader(NAME)(_ctx(tel)) == pytest.approx(1.7)


@pytest.mark.parametrize("ctx", [
    {"driver": types.SimpleNamespace()},                 # no report
    {"driver": types.SimpleNamespace(report=object())},  # no telemetry
    _ctx(None),
    _ctx({"spans": {}, "counts": {}}),
    # a program that keeps its spans and steps but counts no fetches
    _ctx({"spans": {}, "counts": {"splitee.decode.steps": 10}}),
])
def test_reader_finds_nothing_without_the_counter(ctx):
    assert harness.metric_reader(NAME)(ctx) is None
