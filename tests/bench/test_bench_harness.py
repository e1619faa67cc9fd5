"""The harness on the CPU: the registry finds new files by name, the
open-loop clock starts at the due instant, the command refuses to run
without a TPU, and each cell's run reports its metrics."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402

from bench import generator, harness  # noqa: E402

ROOT = bench_testlib.ROOT


def copy_benchmark(dst):
    """BENCHMARK.json and the files under its paths, nothing else."""
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), os.path.join(dst, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return bench


PACED_KIND = """
from bench.harness import log
from bench.kinds.open_loop import OpenLoopDriver


class PacedDriver(OpenLoopDriver):
    def window(self, seconds, stamps=None):
        log("window of the paced kind")
        return super().window(seconds, stamps)


DRIVER = PacedDriver
"""

STEADY_ARRIVALS = """
import numpy as np


def gaps(arrivals, seconds, rng):
    n = int(arrivals["rate_per_s"] * seconds * 1.25) + 8
    return np.full(n, 1.0 / arrivals["rate_per_s"])
"""


def test_registry_finds_new_files_by_name(tmp_path, capsys):
    """A new configuration, traffic mix with a new arrival process and a
    new driver kind, limits and metric are files and entries; no existing
    file of the benchmark changes, and the new cell runs end to end."""
    bench = copy_benchmark(tmp_path)
    b = tmp_path / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    shutil.copy(b / "configs" / "elasticbert12.json",
                b / "configs" / "eb-deep.json")
    shutil.copy(b / "configs" / "elasticbert12.py",
                b / "configs" / "eb-deep.py")
    mix = json.loads((b / "traffic" / "sst2_poisson.json").read_text())
    mix["kind"] = "paced"
    mix["arrivals"] = {"process": "steady", "rate_per_s": 123.0}
    (b / "traffic" / "sst2_steady.json").write_text(json.dumps(mix))
    (b / "kinds" / "paced.py").write_text(PACED_KIND)
    (b / "arrivals" / "steady.py").write_text(STEADY_ARRIVALS)
    (b / "limits" / "eb-deep-steady.json").write_text(
        json.dumps({"score_gap": 0.5}))
    (b / "metrics" / "queue_depth.classify.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "eb-deep", "source": "a test",
                             "file": "bench/configs/eb-deep.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "eb-deep-steady", "config": "eb-deep",
                               "traffic": "sst2_steady", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "queue_depth.classify", "unit": "%",
                               "better": "lower", "source": "program_counter",
                               "layer": "scheduler",
                               "moves": "latency_p95_ms",
                               "workloads": ["eb-deep-steady"]})
    bench["end_to_end"].append({"name": "latency_p95_ms", "unit": "ms",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["eb-deep-steady"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("eb-deep-steady", root=str(tmp_path))
    assert cell.root == str(tmp_path)
    assert cell.traffic["arrivals"]["process"] == "steady"
    assert cell.limits == {"score_gap": 0.5}
    assert hasattr(cell.model, "reference_logits")
    assert {m["name"] for m in cell.end_to_end} == {"latency_p95_ms",
                                                    "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["queue_depth.classify"]
    assert harness.metric_reader("queue_depth.classify",
                                 root=str(tmp_path))({}) == 42.0
    assert harness.driver_class("paced", str(tmp_path)).__name__ \
        == "PacedDriver"

    # the new cell, at the tiny size, through its own kind and arrivals
    cell.spec = bench_testlib.merge(cell.spec,
                                    bench_testlib.TINY_MODEL["elasticbert12"])
    cell.traffic = bench_testlib.merge(cell.traffic, {
        k: v for k, v in bench_testlib.TINY_TRAFFIC["open_loop"].items()
        if k != "arrivals"})
    capsys.readouterr()
    r = harness.run_cell(cell, seed=5, seconds=0.5, trace=False,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1])
    assert "window of the paced kind" in capsys.readouterr().err
    assert r["correct"], r["checks"]
    assert r["attempted"] in (61, 62)          # 123/s for 0.5 s, steadily
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_cells_name_their_metrics():
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))


def _run_cmd(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "eb12-sst2-poisson",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_without_a_tpu_prints_no_result():
    r = _run_cmd(ROOT, {})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not r.stdout.strip()


def test_run_outside_a_checkout_prints_no_result(tmp_path):
    copy_benchmark(tmp_path)
    r = _run_cmd(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert not r.stdout.strip()


def test_arrivals_share_their_gaps_across_seeds():
    arr = {"process": "poisson", "rate_per_s": 500.0, "arrival_seed": 3}
    a = generator.arrival_gaps(arr, 2.0, np.random.default_rng(1))
    b = generator.arrival_gaps(arr, 2.0, np.random.default_rng(2))
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert abs(a.mean() - 1 / 500) < 0.2 / 500


def test_bursty_arrivals_are_the_mix_own():
    """The burst schedule comes from the mix's seed alone, covers the
    window, and its bursts are denser than its quiet phases."""
    arr = {"process": "bursty", "base_rate": 100.0, "burst_rate": 2000.0,
           "mean_on_s": 0.2, "mean_off_s": 0.5, "arrival_seed": 4}
    a = generator.arrival_gaps(arr, 5.0, np.random.default_rng(1))
    b = generator.arrival_gaps(arr, 5.0, np.random.default_rng(2))
    assert np.array_equal(a, b)
    assert a.sum() >= 5.0 and (a > 0).all()
    assert np.percentile(a, 10) < 1 / 1000 < 1 / 200 < np.percentile(a, 95)


def test_classify_tokens_follow_the_domain():
    mix = harness.read_json(os.path.join(ROOT, "bench", "traffic",
                                         "sst2_poisson.json"))
    d = mix["domain"]
    t = generator.classify_tokens(d, 500, 128, np.random.default_rng(0))
    assert t.shape == (500, 128) and t.dtype == np.int32
    assert (t[:, 0] == d["cls_token"]).all()
    body = t[:, 1:]
    signal = (body >= d["signal_base"]) & (
        body < d["signal_base"] + d["num_classes"] * d["num_signals"])
    per_row = signal.sum(1)
    assert per_row.min() >= 2 and per_row.max() <= 8
    assert ((body == d["negation_token"]).sum(1) <= 1).all()
    assert body[~signal & (body != d["negation_token"])].min() \
        >= d["distractor_lo"]


def test_open_loop_latency_counts_a_stall_from_the_due_instant():
    """A stall in the served path lengthens the latency of the requests
    that fell due behind it, measured from when each was due."""
    stall = 0.25

    def stall_once(rt):
        fn, state = rt.edge_fn, {"n": 0}

        def edge_fn(*args):
            state["n"] += 1
            if state["n"] == 12:
                time.sleep(stall)
            return fn(*args)
        rt.edge_fn = edge_fn

    base = bench_testlib.run_tiny("eb12-sst2-poisson", seconds=1.0)
    hit = bench_testlib.run_tiny("eb12-sst2-poisson", seconds=1.0,
                                 runtime_hook=stall_once)
    assert base["correct"] and hit["correct"]
    p95 = lambda r: r["metrics"]["latency_p95_ms"]["value"]  # noqa: E731
    # about stall * rate requests wait behind the stall, some the whole
    # 250 ms; at 150/s that is well over 5% of the window's requests
    assert p95(hit) > p95(base) + 0.5 * stall * 1e3


@pytest.mark.parametrize("cell", ["eb12-imdb512-offline",
                                  "qwen3-1.7b-decode"])
def test_cell_runs_end_to_end(cell):
    r = bench_testlib.run_tiny(cell, seconds=0.5)
    assert r["correct"], r
    assert r["attempted"] > 0 and r["failed"] == 0
    names = {m["name"] for m in harness.load_cell(cell).end_to_end}
    assert set(r["metrics"]) == names
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
