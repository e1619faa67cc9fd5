"""Continuous-batching request scheduler (serving/scheduler.py) and its
`Engine` integration.

Two layers, matching the module's two layers:

* **Pure scheduler invariants** — `RequestScheduler` is a host-side data
  structure, so its contract is pinned directly (fake clock, no JAX):
  property-based under the vendored hypothesis fallback —
  conservation ``submitted == served + shed + pending``, FIFO within
  priority, no request handed out past its shed deadline, batch size <=
  the configured cap — plus unit pins for fill/deadline batch closing,
  admission control, and both shed policies.
* **Differential + fuzz** — the bit-identity ladder's next rung: a
  single-priority, no-deadline scheduler over a steady trace is
  bit-identical (arms, exits, preds, controller state) to the plain
  `Engine` AND the one-shot `serve()` on the same sample order, for the
  batched and sharded(+overlap) paths; a seed-parametrized fuzz
  interleaves submit/tick/drain and re-checks conservation and parity.
"""
import dataclasses
import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                  # pragma: no cover
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.core import CostModel
from repro.data import OnlineStream, make_dataset
from repro.data.synthetic import VOCAB
from repro.serving import (EdgeCloudRuntime, Engine, RequestScheduler,
                           ServingConfig, serve)
from repro.serving.scheduler import (SHED_DEADLINE, SHED_EVICTED,
                                     SHED_QUEUE_FULL, SHED_TENANT_QUOTA)


class FakeClock:
    """Deterministic injectable time source (monotonic seconds)."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def _sample(i: int):
    """A distinguishable stand-in sample (the pure tests never run it)."""
    return {"id": i}


def _sched(**kw):
    kw.setdefault("batch_size", 4)
    clock = kw.pop("clock", None) or FakeClock()
    return RequestScheduler(clock=clock, **kw), clock


# ---------------------------------------------------- formation mechanics

def test_fill_closes_full_batches_fifo():
    s, _ = _sched(batch_size=3)
    for i in range(7):
        s.offer(_sample(i))
    batches = s.poll()
    assert [len(b) for b in batches] == [3, 3]
    assert [[r.sample["id"] for r in b] for b in batches] == [[0, 1, 2],
                                                              [3, 4, 5]]
    assert s.pending == 1
    assert s.poll() == []                    # partial batch keeps waiting


def test_batch_deadline_closes_partial_batch():
    s, clk = _sched(batch_size=4, batch_deadline_ms=50.0)
    s.offer(_sample(0))
    s.offer(_sample(1))
    assert s.poll() == []                    # not full, not due
    clk.advance(0.049)
    assert s.poll() == []                    # 49 ms < 50 ms
    clk.advance(0.002)
    (batch,) = s.poll()
    assert [r.sample["id"] for r in batch] == [0, 1]


def test_next_fire_is_the_earliest_timed_event():
    s, clk = _sched(batch_size=4, batch_deadline_ms=100.0)
    assert s.next_fire() is None             # nothing queued
    s.offer(_sample(0), deadline_ms=60.0)
    assert s.next_fire() == pytest.approx(0.060)   # shed before close
    s.offer(_sample(1), deadline_ms=500.0)
    assert s.next_fire() == pytest.approx(0.060)
    clk.advance(0.070)
    s.poll()                                 # sheds request 0
    assert s.next_fire() == pytest.approx(0.070 + 0.030)  # batch deadline


def test_flush_emits_everything_in_capped_batches():
    s, _ = _sched(batch_size=4)
    for i in range(10):
        s.offer(_sample(i))
    s.poll()                                 # two full batches out
    batches = s.flush()
    assert [len(b) for b in batches] == [2]
    assert s.pending == 0
    assert s.flush() == []                   # idempotent on empty


# --------------------------------------------------- deadlines & shedding

def test_expired_requests_are_shed_never_served():
    s, clk = _sched(batch_size=2)
    s.offer(_sample(0), deadline_ms=10.0)
    s.offer(_sample(1))                      # no deadline
    clk.advance(0.020)
    (batch,) = s.flush()
    assert [r.sample["id"] for r in batch] == [1]
    assert s.shed_reasons[SHED_DEADLINE] == 1
    s.complete(batch)
    assert s.submitted == 2 and s.served == 1 and s.shed == 1


def test_deadline_boundary_is_inclusive_of_now():
    """A request polled exactly AT its deadline is still served (expiry
    is strictly-past: now > deadline)."""
    s, clk = _sched(batch_size=1)
    s.offer(_sample(0), deadline_ms=10.0)
    clk.advance(0.010)
    (batch,) = s.poll()
    assert [r.sample["id"] for r in batch] == [0]


def test_queue_full_reject_sheds_newcomer():
    s, _ = _sched(batch_size=8, max_queue=2, shed_policy="reject")
    assert s.offer(_sample(0)) and s.offer(_sample(1))
    assert not s.offer(_sample(2))
    assert s.shed_reasons[SHED_QUEUE_FULL] == 1
    assert [r.sample["id"] for r in s.flush()[0]] == [0, 1]


def test_drop_oldest_evicts_lowest_priority_oldest():
    s, _ = _sched(batch_size=8, max_queue=2, shed_policy="drop_oldest")
    s.offer(_sample(0), priority=0)
    s.offer(_sample(1), priority=0)
    assert s.offer(_sample(2), priority=5)   # evicts 0 (lowest, oldest)
    assert s.shed_reasons[SHED_EVICTED] == 1
    # a newcomer no more important than anything queued is the victim
    assert not s.offer(_sample(3), priority=0)
    assert s.shed_reasons[SHED_QUEUE_FULL] == 1
    served = [r.sample["id"] for r in s.flush()[0]]
    assert served == [2, 1]                  # priority-major order


def test_priority_major_fifo_within():
    s, _ = _sched(batch_size=6)
    order = [(0, 0), (1, 1), (2, 0), (3, 1), (4, 0), (5, 1)]
    for i, prio in order:
        s.offer(_sample(i), priority=prio)
    (batch,) = s.poll()
    assert [r.sample["id"] for r in batch] == [1, 3, 5, 0, 2, 4]


# ------------------------------------------------ property-based invariants

def _drive_random(seed: int):
    """Random scheduler workload; returns (scheduler, served batches)."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 6))
    s, clk = _sched(
        batch_size=B,
        max_queue=int(rng.integers(0, 3) * B),
        batch_deadline_ms=float(rng.choice([0.0, 5.0, 40.0])),
        shed_policy=str(rng.choice(["reject", "drop_oldest"])))
    served = []
    sid = 0
    for _ in range(int(rng.integers(5, 40))):
        op = rng.random()
        if op < 0.7:                              # a burst of offers
            for _ in range(int(rng.integers(1, 3 * B + 1))):
                s.offer(_sample(sid),
                        priority=int(rng.integers(0, 3)),
                        deadline_ms=(float(rng.integers(1, 100))
                                     if rng.random() < 0.5 else None))
                sid += 1
        clk.advance(float(rng.random()) * 0.03)
        for batch in (s.flush() if op > 0.95 else s.poll()):
            assert batch, "formed batches are never empty"
            served.append((clk.t, batch))
            s.complete(batch, clk.t)
    for batch in s.flush():
        served.append((clk.t, batch))
        s.complete(batch, clk.t)
    return s, served


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_conservation(seed):
    """submitted == served + shed + pending, at the end and bitwise in
    the snapshot section."""
    s, served = _drive_random(seed)
    assert s.pending == 0
    assert s.submitted == s.served + s.shed
    assert s.served == sum(len(b) for _, b in served)
    snap = s.snapshot()
    assert snap["submitted"] == snap["served"] + snap["shed"]
    assert snap["shed"] == sum(snap["shed_reasons"].values())
    assert snap["latency_ms"]["count"] == snap["served"]


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_batch_size_capped(seed):
    s, served = _drive_random(seed)
    assert all(1 <= len(b) <= s.batch_size for _, b in served)


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_fifo_within_priority(seed):
    """Service order restricted to any one priority is admission order."""
    _, served = _drive_random(seed)
    flat = [r for _, batch in served for r in batch]
    for prio in {r.priority for r in flat}:
        seqs = [r.seq for r in flat if r.priority == prio]
        assert seqs == sorted(seqs), f"priority {prio} served out of order"


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_no_request_served_past_deadline(seed):
    """Every request handed out carries deadline >= formation time."""
    _, served = _drive_random(seed)
    for formed_at, batch in served:
        for r in batch:
            assert r.deadline is None or r.deadline >= formed_at, (
                f"request {r.seq} served {formed_at - r.deadline:.4f}s "
                f"past its shed deadline")


# ------------------------------------- Engine integration (differential)

@pytest.fixture(scope="module")
def served():
    import jax
    from repro.models.api import build_model
    base = get_smoke_config("elasticbert12")
    cfg = dataclasses.replace(
        base, num_layers=3, d_model=32, num_heads=2, num_kv_heads=2,
        d_ff=128, vocab_size=VOCAB, num_classes=2, dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eval_data = make_dataset("imdb_like", 160, seed=2, seq_len=16)
    rt = EdgeCloudRuntime(cfg)
    cost = CostModel(num_layers=cfg.num_layers, alpha=0.6, offload=3.0)
    return cfg, params, rt, cost, eval_data


def _assert_bit_identical(got, ref):
    assert got["n"] == ref["n"]
    np.testing.assert_array_equal(got["arms"], ref["arms"])
    np.testing.assert_array_equal(got["preds"], ref["preds"])
    np.testing.assert_array_equal(got["rewards"], ref["rewards"])
    np.testing.assert_array_equal(got["exited"], ref["exited"])
    assert got["cost_total"] == ref["cost_total"]
    assert got.get("accuracy") == ref.get("accuracy")
    np.testing.assert_array_equal(got["state"]["q"], ref["state"]["q"])
    np.testing.assert_array_equal(got["state"]["n"], ref["state"]["n"])
    assert got["state"]["t"] == ref["state"]["t"]


def _samples(eval_data, n):
    return list(itertools.islice(iter(OnlineStream(eval_data, seed=0)), n))


def test_scheduled_engine_parity_batched(served):
    """The differential rung: a single-priority, no-deadline scheduler
    over a steady trace is bit-identical to the plain Engine AND the
    one-shot serve() on the same sample order."""
    _, params, rt, cost, eval_data = served
    samples = _samples(eval_data, 60)                # ragged tail: 60 % 8
    plain_cfg = ServingConfig(batch_size=8)
    sched_cfg = dataclasses.replace(plain_cfg, scheduler="fifo")

    plain = Engine(rt, params, cost, plain_cfg)
    sched = Engine(rt, params, cost, sched_cfg)
    for i in range(0, len(samples), 5):              # same ragged bursts
        plain.submit(samples[i:i + 5])
        sched.submit(samples[i:i + 5])
    got, ref = sched.close(), plain.close()
    _assert_bit_identical(got, ref)
    oneshot = serve(rt, params, samples, cost, plain_cfg)
    _assert_bit_identical(got, oneshot)
    # the scheduler section closes its ledger without shedding anything
    assert got.scheduler["served"] == 60
    assert got.scheduler["shed"] == 0 and got.scheduler["dropped"] == 0
    assert got.scheduler["latency_ms"]["count"] == 60
    assert got.scheduler["latency_ms"]["p50"] <= \
        got.scheduler["latency_ms"]["p99"]
    assert ref.scheduler is None                     # plain path: no section


def test_scheduled_engine_parity_sharded_overlap(served):
    """Scheduler-formed batches feed the depth-K overlap ring exactly as
    buffer-formed ones do."""
    _, params, rt, cost, eval_data = served
    samples = _samples(eval_data, 80)
    cfg = ServingConfig(path="sharded", batch_size=16, overlap=True,
                        overlap_depth=2)
    eng = Engine(rt, params, cost,
                 dataclasses.replace(cfg, scheduler="fifo"))
    for s in samples:
        eng.submit(s)
    got = eng.close()
    ref = serve(rt, params, samples, cost, cfg)
    _assert_bit_identical(got, ref)
    assert got["overlap"] == ref["overlap"]


def test_scheduled_serve_facade_parity(served):
    """serve() with a scheduler config routes through an Engine and
    stays on the ladder."""
    _, params, rt, cost, eval_data = served
    ref = serve(rt, params, OnlineStream(eval_data, seed=0), cost,
                ServingConfig(batch_size=8, max_samples=48))
    got = serve(rt, params, OnlineStream(eval_data, seed=0), cost,
                ServingConfig(batch_size=8, max_samples=48,
                              scheduler="fifo"))
    _assert_bit_identical(got, ref)
    assert got.scheduler["served"] == 48


def test_engine_tick_closes_partial_batch_on_deadline(served):
    _, params, rt, cost, eval_data = served
    clk = FakeClock()
    eng = Engine(rt, params, cost,
                 ServingConfig(batch_size=8, scheduler="fifo",
                               batch_deadline_ms=25.0), clock=clk)
    eng.submit(_samples(eval_data, 3))
    assert eng.tick() == 0 and eng.pending == 3      # not due yet
    clk.advance(0.030)
    assert eng.tick() == 3 and eng.pending == 0      # deadline close
    rep = eng.close()
    assert rep.n == 3
    assert rep.scheduler["batches"] == 1
    assert rep.scheduler["mean_batch_fill"] == pytest.approx(3 / 8)


def test_engine_telemetry_times_queue_wait_on_the_scheduler_clock(served):
    """Each request's wait from arrival to the batch that takes it is
    added on the scheduler's clock; batch formation is a span per poll or
    flush, and the batched session counts its batches, rows and cloud
    launches inside its push spans."""
    _, params, rt, cost, eval_data = served
    clk = FakeClock()
    eng = Engine(rt, params, cost,
                 ServingConfig(batch_size=8, scheduler="fifo",
                               batch_deadline_ms=25.0), clock=clk)
    samples = _samples(eval_data, 13)
    eng.submit(samples[:3])                          # 3 wait 30 ms
    clk.advance(0.010)
    eng.submit(samples[3:5])                         # 2 wait 20 ms
    clk.advance(0.020)
    assert eng.tick() == 5
    eng.submit(samples[5:13])                        # a full batch: 0 ms
    clk.advance(0.004)
    rep = eng.close()
    sp, cnt = rep.telemetry["spans"], rep.telemetry["counts"]
    wait = sp["splitee.sched.queue_wait"]
    assert wait["n"] == 13
    assert wait["total_ms"] == pytest.approx(3 * 30.0 + 2 * 20.0)
    # one formation per submit, tick and drain
    assert sp["splitee.sched.form"]["n"] == 5
    assert cnt["splitee.batched.batches"] == 2
    assert sp["splitee.batched.push"]["n"] == 2
    assert cnt["splitee.batched.rows"] == rep.n == 13
    offloaded = int((~rep.exited).sum())
    assert (cnt["splitee.batched.cloud_launches"] > 0) == (offloaded > 0)
    assert cnt["splitee.batched.cloud_launches"] <= len(set(rep.arms))
    parts = sum(sp[f"splitee.batched.{k}"]["total_ms"]
                for k in ("select", "edge", "cloud", "fold"))
    push = sp["splitee.batched.push"]
    assert push["self_ms"] == pytest.approx(push["total_ms"] - parts,
                                            abs=1e-6)


def test_engine_sheds_expired_and_overflow(served):
    _, params, rt, cost, eval_data = served
    clk = FakeClock()
    eng = Engine(rt, params, cost,
                 ServingConfig(batch_size=4, scheduler="fifo",
                               max_queue=3, shed_policy="reject"),
                 clock=clk)
    samples = _samples(eval_data, 8)
    for s in samples[:3]:
        assert eng.submit(s, deadline_ms=10.0) == 1
    assert eng.submit(samples[3]) == 0               # queue full: shed
    clk.advance(0.020)                               # all 3 expire
    rep = eng.close()
    assert rep.n == 0
    assert eng.shed == 4
    assert rep.scheduler["shed_reasons"] == {
        "queue_full": 1, "evicted": 0, "deadline": 3, "tenant_quota": 0}
    assert eng.submitted == rep.n + eng.shed + eng.dropped == 4


def test_engine_priority_and_deadline_require_scheduler(served):
    _, params, rt, cost, eval_data = served
    eng = Engine(rt, params, cost, ServingConfig(batch_size=4))
    with pytest.raises(ValueError, match="scheduler"):
        eng.submit(_samples(eval_data, 1), priority=2)
    with pytest.raises(ValueError, match="scheduler"):
        eng.submit(_samples(eval_data, 1), deadline_ms=5.0)
    assert eng.tick() == 0                           # no-op without one
    eng.close()


def test_engine_cap_composes_with_scheduler(served):
    """max_samples drops land in `dropped`, scheduler sheds in `shed`,
    and the conservation ledger still closes."""
    _, params, rt, cost, eval_data = served
    eng = Engine(rt, params, cost,
                 ServingConfig(batch_size=4, scheduler="fifo",
                               max_samples=6))
    rep = None
    assert eng.submit(_samples(eval_data, 10)) == 6
    rep = eng.close()
    assert rep.n == 6 and eng.dropped == 4 and eng.shed == 0
    assert eng.submitted == 10
    assert rep.scheduler["dropped"] == 4


# --------------------------------------------------------- fuzz (seeded)

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fuzz_interleaving_parity_and_conservation(served, seed):
    """Seed-parametrized fuzz: interleave submit (dict vs list, sizes
    1..3B) and scheduler ticks over a few hundred samples, drain once at
    the end; conservation holds and the result is bit-identical to a
    one-shot serve() on the same sample order."""
    _, params, rt, cost, eval_data = served
    rng = np.random.default_rng(seed)
    B = int(rng.integers(2, 9))
    cfg = ServingConfig(batch_size=B)
    samples = _samples(eval_data, 160)
    eng = Engine(rt, params, cost,
                 dataclasses.replace(cfg, scheduler="fifo"))
    i = 0
    while i < len(samples):
        if rng.random() < 0.15:
            eng.tick()          # no deadlines: ticks never change anything
        if rng.random() < 0.3:                       # single dict
            eng.submit(samples[i])
            i += 1
        else:                                        # ragged list burst
            k = int(rng.integers(1, 3 * B + 1))
            eng.submit(samples[i:i + k])
            i += len(samples[i:i + k])
    rep = eng.close()
    assert eng.submitted == rep.n + eng.shed + eng.dropped == len(samples)
    _assert_bit_identical(rep, serve(rt, params, samples, cost, cfg))


@pytest.mark.parametrize("seed", [3, 4])
def test_fuzz_mid_drains_conserve_and_grow(served, seed):
    """With drains interleaved mid-stream the batch schedule legitimately
    diverges from the one-shot replay (ragged flushes), but conservation
    and report monotonicity must survive any interleaving."""
    _, params, rt, cost, eval_data = served
    rng = np.random.default_rng(seed)
    B = int(rng.integers(2, 7))
    eng = Engine(rt, params, cost,
                 ServingConfig(batch_size=B, scheduler="fifo",
                               max_queue=2 * B, shed_policy="drop_oldest"))
    samples = _samples(eval_data, 120)
    last_n = 0
    i = 0
    while i < len(samples):
        k = int(rng.integers(1, 3 * B + 1))
        eng.submit(samples[i:i + k],
                   priority=int(rng.integers(0, 3)))
        i += len(samples[i:i + k])
        assert eng.submitted == i
        # the ledger closes mid-stream too (n of already-served samples
        # lives on the session until the next report)
        assert eng.submitted == eng._sess.n + eng.pending + eng.shed \
            + eng.dropped
        if rng.random() < 0.3:
            n = eng.drain().n
            assert n >= last_n and eng.pending == 0
            last_n = n
    rep = eng.close()
    assert rep.n >= last_n
    assert eng.submitted == rep.n + eng.shed + eng.dropped == len(samples)


# ------------------------------------------------------- tenant support

def test_tenantless_snapshot_has_no_tenant_section():
    s, _ = _sched(batch_size=2)
    s.offer(_sample(0))
    s.complete(s.flush()[0])
    assert "tenants" not in s.snapshot()


def test_tenant_batches_are_pure_and_capped():
    s, _ = _sched(batch_size=1, tenant_batch_size={"a": 3, "b": 2})
    for i in range(7):
        s.offer(_sample(i), tenant="a" if i % 2 == 0 else "b")
    batches = s.poll()
    # a has 4 queued (cap 3 -> one full batch), b has 3 (cap 2 -> one)
    assert [len(b) for b in batches] == [3, 2]
    for b in batches:
        assert len({r.tenant for r in b}) == 1
    tail = s.flush()
    assert sorted(len(b) for b in tail) == [1, 1]
    for b in batches + tail:
        s.complete(b)
    snap = s.snapshot()
    assert snap["tenants"]["a"] == {
        "submitted": 4, "served": 4, "shed": 0, "batches": 2, "pending": 0}
    assert snap["tenants"]["b"] == {
        "submitted": 3, "served": 3, "shed": 0, "batches": 2, "pending": 0}
    # conservation holds globally AND per tenant
    assert snap["submitted"] == snap["served"] + snap["shed"] \
        + snap["pending"] == 7


def test_tenant_quota_reject_sheds_newcomer():
    s, _ = _sched(batch_size=4, tenant_quota={"a": 2})
    assert s.offer(_sample(0), tenant="a")
    assert s.offer(_sample(1), tenant="a")
    assert not s.offer(_sample(2), tenant="a")       # over quota
    assert s.offer(_sample(3), tenant="b")           # b unaffected
    assert s.shed_reasons[SHED_TENANT_QUOTA] == 1
    snap = s.snapshot()
    assert snap["tenants"]["a"]["shed"] == 1
    assert snap["tenants"]["b"]["shed"] == 0


def test_tenant_quota_drop_oldest_evicts_within_tenant():
    s, _ = _sched(batch_size=4, shed_policy="drop_oldest",
                  tenant_quota={"a": 2})
    s.offer(_sample(0), tenant="a", priority=0)
    s.offer(_sample(1), tenant="a", priority=1)
    s.offer(_sample(9), tenant="b", priority=0)      # lower than newcomer
    # high-priority newcomer evicts a's own oldest low-priority request,
    # never touching b's queue
    assert s.offer(_sample(2), tenant="a", priority=2)
    ids = {r.sample["id"] for r in s._queue}
    assert ids == {1, 9, 2}
    assert s.shed_reasons[SHED_EVICTED] == 1
    # a low-priority newcomer at quota is itself shed
    assert not s.offer(_sample(3), tenant="a", priority=0)
    assert s.shed_reasons[SHED_TENANT_QUOTA] == 1


def test_tenant_fairness_least_recently_served():
    s, _ = _sched(batch_size=2)
    for i in range(4):
        s.offer(_sample(i), tenant="a")
        s.offer(_sample(10 + i), tenant="b")
    order = [b[0].tenant for b in s.poll()]
    # both fill twice; service alternates instead of draining one tenant
    assert order == ["a", "b", "a", "b"]


def test_tenant_deadline_closes_partial_tenant_batch():
    s, clk = _sched(batch_size=8, batch_deadline_ms=50.0)
    s.offer(_sample(0), tenant="a")
    clk.advance(0.030)
    s.offer(_sample(1), tenant="b")
    clk.advance(0.025)                 # a is 55ms old, b only 25ms
    batches = s.poll()
    assert len(batches) == 1 and batches[0][0].tenant == "a"
    assert s.pending == 1


@given(st.integers(0, 10**6))
@settings(max_examples=20, deadline=None)
def test_tenant_conservation_property(seed):
    """Random interleavings of tenant-labeled offers, polls, and flushes:
    conservation holds per tenant, every formed batch is tenant-pure and
    within its tenant's cap."""
    rng = np.random.default_rng(seed)
    caps = {"a": int(rng.integers(1, 4)), "b": int(rng.integers(1, 4))}
    quota = {"a": int(rng.integers(1, 5))}
    s, _ = _sched(batch_size=int(rng.integers(1, 4)),
                  tenant_batch_size=caps, tenant_quota=quota)
    tenants = ["a", "b", None]
    for i in range(int(rng.integers(5, 40))):
        t = tenants[int(rng.integers(0, 3))]
        s.offer(_sample(i), tenant=t,
                priority=int(rng.integers(0, 3)))
        if rng.integers(0, 3) == 0:
            for b in s.poll():
                assert len({r.tenant for r in b}) == 1
                cap = caps.get(b[0].tenant, s.batch_size)
                assert len(b) <= cap
                s.complete(b)
    for b in s.flush():
        assert len({r.tenant for r in b}) == 1
        s.complete(b)
    snap = s.snapshot()
    assert snap["submitted"] == snap["served"] + snap["shed"]
    assert snap["pending"] == 0
    for led in snap.get("tenants", {}).values():
        assert led["submitted"] == led["served"] + led["shed"]
