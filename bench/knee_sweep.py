#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee: the
highest rate the served path sustains with no growing backlog.

    python3 bench/knee_sweep.py --workload eb12-sst2-poisson \
        --seconds 6 --rates 1000 1500 2000 2500 3000 3500 4000

One process, one set-up; each rate runs a fresh ``Engine`` for the
window. Prints per rate the requests due, the samples served per second
while the window was open, the backlog left when it closed (due but not
served), the generator's lateness and the latency quantiles from the
due instant; then the knee and the cell's rate, 0.8 of it. A rate is
sustained when the window served at least 97% of it and its 95th
percentile stayed under 250 ms; the knee is the highest rate below the
first one that is not. Needs the accelerator, like ``run.py``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVED_SHARE = 0.97
P95_MS = 250.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("knee_sweep: needs a TPU", file=sys.stderr)
        return 2
    driver = harness.driver_class(cell.traffic["kind"], cell.root)(
        cell, seed=args.seed, seconds=args.seconds,
        devices=jax.devices()[:cell.chips],
        probe=harness.Probe(annotate=False))
    driver.setup()
    knee = None
    for rate in sorted(args.rates):
        driver.traffic = dict(driver.traffic,
                              arrivals=dict(driver.traffic["arrivals"],
                                            rate_per_s=rate))
        driver.make_inputs()
        stamps = {}
        r = driver.window(args.seconds, stamps=stamps)
        due, done = stamps["due"], stamps["done"]
        t_end = stamps["t_end"]
        n_due = len(due)
        served_open = int(np.sum(done <= t_end))
        lat = r["latency_ms"]
        row = {"rate": rate, "due": n_due,
               "served_per_s_in_window": served_open / args.seconds,
               "backlog_at_close": n_due - served_open,
               "latency_p50_ms": float(np.percentile(lat, 50)),
               "latency_p95_ms": float(np.percentile(lat, 95)),
               "latency_max_ms": float(np.max(lat)),
               "lateness_p95_ms": stamps["lateness_p95_ms"]}
        row["sustained"] = bool(
            row["served_per_s_in_window"] >= SERVED_SHARE * n_due
            / args.seconds and row["latency_p95_ms"] < P95_MS)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
        knee = rate
    print(json.dumps({"knee": knee,
                      "rate": None if knee is None else 0.8 * knee,
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
