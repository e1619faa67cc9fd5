"""Runs with the timed path broken underneath: each must come out with
``correct`` false. The harness's look for a chip is skipped; everything
else of a run (set-up, window, comparison with the reference, the limits
of the cell) is the benchmark's own, at a size the CPU holds."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_testlib  # noqa: E402


def _half(a):
    """Rows of the second half replaced by the first half's: half the
    batch left out and its answers taken from the rest."""
    a = jnp.asarray(a)
    n = a.shape[0]
    h = n // 2
    if h == 0:
        return a
    idx = np.concatenate([np.arange(n - h), np.arange(h)])
    return a[idx]


def classify_fault(kind):
    def hook(rt):
        edge, cloud = rt.edge_fn, rt.cloud_fn
        if kind == "answer":
            rt.edge_fn = lambda p, b, d: (lambda c, y, h: (c, (y + 1) % 2, h))(
                *edge(p, b, d))
            rt.cloud_fn = lambda p, h, d: (lambda c, y: (c, (y + 1) % 2))(
                *cloud(p, h, d))
        elif kind == "half":
            rt.edge_fn = lambda p, b, d: tuple(_half(x) for x in edge(p, b, d))
            rt.cloud_fn = lambda p, h, d: tuple(_half(x)
                                                for x in cloud(p, h, d))
    return hook


def decode_fault(kind):
    def hook(rt):
        edge, cloud = rt.edge_fn, rt.cloud_fn
        V = rt.cfg.vocab_size

        def edge_fn(params, caches, tok, step, depths, total):
            logits, conf, pred, conf_f, pred_f, hid, new = edge(
                params, caches, tok, step, depths, total)
            if kind == "answer":
                pred, pred_f = (pred + 1) % V, (pred_f + 1) % V
            elif kind == "state":
                new = caches
            elif kind == "half":
                pred, pred_f = _half(pred.T).T, _half(pred_f)
            return logits, conf, pred, conf_f, pred_f, hid, new

        def cloud_fn(params, caches, hid, step, depths, active, total):
            logits, conf_l, pred_l, new = cloud(params, caches, hid, step,
                                                depths, active, total)
            if kind == "answer":
                pred_l = (pred_l + 1) % V
            elif kind == "state":
                new = caches
            elif kind == "half":
                pred_l = _half(pred_l)
            return logits, conf_l, pred_l, new

        rt.edge_fn, rt.cloud_fn = edge_fn, cloud_fn
    return hook


@pytest.mark.parametrize("cell,kind", [
    ("eb12-sst2-poisson", "answer"),
    ("eb12-sst2-poisson", "half"),
    ("eb12-imdb512-offline", "answer"),
    ("eb12-imdb512-offline", "half"),
])
def test_classify_fault_is_caught(cell, kind):
    r = bench_testlib.run_tiny(cell, seconds=0.4,
                               runtime_hook=classify_fault(kind))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("kind", ["answer", "state", "half"])
def test_decode_fault_is_caught(kind):
    r = bench_testlib.run_tiny("qwen3-1.7b-decode", seconds=0.4,
                               runtime_hook=decode_fault(kind))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", ["eb12-sst2-poisson", "qwen3-1.7b-decode"])
def test_sound_run_is_correct(cell):
    r = bench_testlib.run_tiny(cell, seconds=0.4)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("cell", ["eb12-sst2-poisson", "eb12-imdb512-offline",
                                  "qwen3-1.7b-decode"])
def test_control_is_caught(cell):
    """The reference one precision step below the configuration's, put in
    the program's place, comes out not correct by the run's own verdict,
    while the program in the same run is correct."""
    r = bench_testlib.run_tiny(cell, seconds=0.4, control=True)
    limits = {k: v["limit"] for k, v in r["checks"].items()}
    assert r["correct"], r["checks"]
    assert r["control_correct"] is False, (r["control"], limits)
    assert any(r["control"][k] > limits[k] for k in limits), r["control"]
