"""The serving sessions' in-program tracer (serving/tracing.py): span
totals and self time, counters and out-of-stack durations, per-thread
parent stacks, and the snapshot's shape."""
import sys
import threading
import time

import pytest

from repro.serving.tracing import Tracer


def _sleep_ms(ms):
    t_end = time.perf_counter() + ms / 1e3
    while time.perf_counter() < t_end:
        pass


def test_nested_spans_charge_children_to_the_parent():
    tr = Tracer()
    with tr.span("splitee.a"):
        _sleep_ms(2)
        with tr.span("splitee.b"):
            _sleep_ms(3)
            with tr.span("splitee.c", step=1):
                _sleep_ms(1)
        with tr.span("splitee.b"):
            _sleep_ms(1)
    sp = tr.snapshot()["spans"]
    assert {k: v["n"] for k, v in sp.items()} == {
        "splitee.a": 1, "splitee.b": 2, "splitee.c": 1}
    a, b, c = sp["splitee.a"], sp["splitee.b"], sp["splitee.c"]
    # self time is the total less the direct children's totals
    assert a["self_ms"] == pytest.approx(a["total_ms"] - b["total_ms"],
                                         abs=1e-6)
    assert b["self_ms"] == pytest.approx(b["total_ms"] - c["total_ms"],
                                         abs=1e-6)
    assert c["self_ms"] == c["total_ms"] >= 1.0
    assert a["total_ms"] >= b["total_ms"] + 2.0
    assert b["total_ms"] >= c["total_ms"] + 4.0
    assert all(v["self_ms"] >= 0 for v in sp.values())


def test_a_span_closed_by_an_exception_is_recorded():
    tr = Tracer()
    with pytest.raises(KeyError):
        with tr.span("splitee.outer"):
            with tr.span("splitee.inner"):
                raise KeyError("x")
    sp = tr.snapshot()["spans"]
    assert sp["splitee.outer"]["n"] == sp["splitee.inner"]["n"] == 1
    with tr.span("splitee.after"):          # the stack was unwound
        pass
    assert tr.snapshot()["spans"]["splitee.after"]["self_ms"] == \
        tr.snapshot()["spans"]["splitee.after"]["total_ms"]


def test_counts_and_added_durations():
    tr = Tracer()
    tr.count("splitee.x")
    tr.count("splitee.x", 4)
    tr.count("splitee.y", 0)
    tr.add("splitee.wait", 2_500_000)
    tr.add("splitee.wait", 500_000.7)
    with tr.span("splitee.s"):
        tr.add("splitee.wait", 1_000_000)   # not a child of the open span
    snap = tr.snapshot()
    assert snap["counts"] == {"splitee.x": 5, "splitee.y": 0}
    w = snap["spans"]["splitee.wait"]
    assert w["n"] == 3
    assert w["total_ms"] == w["self_ms"] == pytest.approx(4.0)
    s = snap["spans"]["splitee.s"]
    assert s["self_ms"] == s["total_ms"]


def test_snapshot_shape_and_copy():
    tr = Tracer()
    assert tr.snapshot() == {"spans": {}, "counts": {}}
    with tr.span("splitee.s", push=0, step=3):
        tr.count("splitee.n")
    snap = tr.snapshot()
    assert set(snap) == {"spans", "counts"}
    rec = snap["spans"]["splitee.s"]
    assert set(rec) == {"n", "total_ms", "self_ms"}
    assert isinstance(rec["n"], int) and isinstance(rec["total_ms"], float)
    snap["counts"]["splitee.n"] = 99
    snap["spans"]["splitee.s"]["n"] = 99
    again = tr.snapshot()
    assert again["counts"]["splitee.n"] == 1
    assert again["spans"]["splitee.s"]["n"] == 1


def test_parent_stacks_are_per_thread():
    """A span open on one thread is no parent of a span on another: the
    other thread's time is not taken off its self time."""
    tr = Tracer()
    started, release = threading.Event(), threading.Event()

    def other():
        with tr.span("splitee.other"):
            started.set()
            release.wait(5)
            _sleep_ms(5)

    th = threading.Thread(target=other)
    with tr.span("splitee.main"):
        th.start()
        assert started.wait(5)
        release.set()
        th.join(5)
    assert not th.is_alive()
    sp = tr.snapshot()["spans"]
    main, oth = sp["splitee.main"], sp["splitee.other"]
    assert main["self_ms"] == main["total_ms"] >= 5.0
    assert oth["self_ms"] == oth["total_ms"] >= 5.0


def test_concurrent_updates_are_not_lost():
    """More threads than cores, switching often: every span and count of
    every thread lands in the totals."""
    tr = Tracer()
    threads, reps = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(reps):
                with tr.span("splitee.outer"):
                    with tr.span("splitee.inner"):
                        tr.count("splitee.k", 2)
                tr.add("splitee.wait", 1)

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(60)
        assert not any(th.is_alive() for th in pool)
    finally:
        sys.setswitchinterval(old)
    snap = tr.snapshot()
    n = threads * reps
    assert snap["counts"]["splitee.k"] == 2 * n
    assert snap["spans"]["splitee.outer"]["n"] == n
    assert snap["spans"]["splitee.inner"]["n"] == n
    assert snap["spans"]["splitee.wait"]["n"] == n
    assert snap["spans"]["splitee.wait"]["total_ms"] == pytest.approx(n / 1e6)
    outer = snap["spans"]["splitee.outer"]
    inner = snap["spans"]["splitee.inner"]
    assert outer["self_ms"] == pytest.approx(
        outer["total_ms"] - inner["total_ms"], abs=1e-6)
