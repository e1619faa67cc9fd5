"""Offline classify traffic: a saturating stream through ``serve()``."""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import generator as gen
from bench.driver import ClassifyDriver
from bench.harness import log


class OfflineDriver(ClassifyDriver):
    """A saturating stream through ``serve()``: pulls stop at the first
    batch boundary after the window, and the rate is the samples served
    over the time until ``serve()`` returned."""

    def make_inputs(self):
        t = self.traffic
        self.pool = gen.classify_tokens(t["domain"], int(t["pool_rows"]),
                                        t["seq_len"], self.rng)

    def window(self, seconds: float) -> Dict[str, Any]:
        from repro.serving import serve
        pool = self.pool
        B = self.scfg.batch_size
        probe = self.probe
        self.batches = 0

        def stream():
            i = 0
            while True:
                if i % B == 0:
                    if time.perf_counter() >= t_end:
                        return
                    self.batches += 1
                yield {"tokens": pool[i % len(pool)]}
                i += 1

        probe.counting = True
        t0 = time.perf_counter()
        t_end = t0 + seconds
        with probe.span("window"):
            self.report = serve(self.runtime, self.params, stream(),
                                self.cost, self.scfg)
            elapsed = time.perf_counter() - t0
        probe.counting = False
        self.window_s = elapsed
        n = self.report.n
        log(f"offline: {n} samples in {elapsed:.3f} s, {self.batches} "
            f"batches, offload share {self.report.offload_frac:.3f}")
        return {"attempted": n, "failed": 0,
                "end_to_end": {"samples_per_s": n / elapsed}}

    def served_tokens(self, idx):
        return self.pool[np.asarray(idx) % len(self.pool)]

    def useful_flops(self) -> float:
        return self.served_flops(self.report.n)


DRIVER = OfflineDriver
