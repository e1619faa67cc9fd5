"""Open-loop classify traffic: single-sample requests arrive on the
mix's arrival process (``bench/arrivals/<process>.py``) and go through
``Engine`` with the request scheduler; each request is timed from the
instant it was due."""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import generator as gen
from bench.driver import ClassifyDriver, quantiles
from bench.harness import log, percentile


class OpenLoopDriver(ClassifyDriver):
    """Single-sample requests into ``Engine`` at the mix's arrivals."""

    def make_inputs(self):
        t = self.traffic
        self.gaps = gen.arrival_gaps(t["arrivals"], self.seconds, self.rng,
                                     self.cell.root)
        self.tokens = gen.classify_tokens(t["domain"], len(self.gaps),
                                          t["seq_len"], self.rng)

    def window(self, seconds: float, stamps=None) -> Dict[str, Any]:
        """``stamps``, when given, receives the due and completion
        instants of the requests due in the window (the knee sweep)."""
        from repro.serving import Engine
        gaps = self.gaps
        n = len(gaps)
        eng = Engine(self.runtime, self.params, self.cost, self.scfg,
                     clock=time.perf_counter)
        sess = eng._sess
        done = np.full(n, np.nan)
        inner = sess.push
        probe = self.probe
        self.batches = 0

        def push(batch):
            with probe.span("push"):
                inner(batch)
            now = time.perf_counter()
            for s in batch:
                done[s["rid"]] = now
            if probe.counting:
                self.batches += 1

        sess.push = push
        sched = eng.scheduler
        submitted = np.full(n, np.nan)
        tokens = self.tokens
        i = 0
        probe.counting = True
        t0 = time.perf_counter()
        due = t0 + np.cumsum(gaps)
        t_end = t0 + seconds
        n_due = int(np.searchsorted(due, t_end))
        with probe.span("window"):
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                while i < n_due and due[i] <= now:
                    submitted[i] = now
                    with probe.span("submit"):
                        eng.submit({"tokens": tokens[i], "rid": i})
                    i += 1
                    now = time.perf_counter()
                with probe.span("tick"):
                    eng.tick()
                wake = min(due[i] if i < n_due else t_end, t_end)
                fire = sched.next_fire()
                if fire is not None:
                    wake = min(wake, fire)
                dt = wake - time.perf_counter()
                if dt > 0:
                    with probe.span("generator_wait"):
                        time.sleep(dt)
        probe.counting = False
        late = submitted[:i] - due[:i]
        # requests due in the window that the loop had not reached: late
        while i < n_due:
            submitted[i] = time.perf_counter()
            eng.submit({"tokens": tokens[i], "rid": i})
            i += 1
        self.report = eng.close()
        self.window_s = seconds
        lat_ms = (done[:n_due] - due[:n_due]) * 1e3
        missing = int(np.isnan(lat_ms).sum())
        lat_ms = np.where(np.isnan(lat_ms), np.inf, lat_ms)
        self.snapshot = self.report.scheduler
        log(f"open loop: {n_due} requests due in {seconds} s "
            f"({n_due / seconds:.1f}/s), {missing} missing, "
            f"{self.batches} batches")
        log(f"generator lateness (ms): {quantiles(late * 1e3)}; "
            f"{n_due - len(late)} submitted after the window closed")
        log(f"latency (ms) from due: {quantiles(lat_ms)}")
        if stamps is not None:
            stamps.update(due=due[:n_due], done=done[:n_due], t_end=t_end,
                          lateness_p95_ms=percentile(late * 1e3, 95)
                          if len(late) else 0.0)
        return {"attempted": n_due, "failed": missing, "latency_ms": lat_ms,
                "end_to_end": {"latency_p95_ms": percentile(lat_ms, 95)}}

    def served_tokens(self, idx):
        return self.tokens[idx]

    def useful_flops(self) -> float:
        return self.served_flops(self.report.n)


DRIVER = OpenLoopDriver
