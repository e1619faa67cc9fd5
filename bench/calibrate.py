#!/usr/bin/env python3
"""Readings from which a cell's limits are set: the program's numbers
and the lower-precision control's over many seeds, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 101 102 ... [--control-seeds 101 102 103]

Each seed is a whole run (weights, probe, warm-up, window, comparison),
so the readings are those of the timed path at the timed sizes. Prints
one JSON line per seed and a summary: the largest program reading of
each number (the lower reading) and the smallest control reading (the
upper one). On a control seed the control is judged against the cell's
limits as the program is (``control_correct``). Needs the accelerator,
like ``run.py``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    import jax
    if jax.devices()[0].platform != "tpu" or \
            len(jax.devices()) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    lower, upper = {}, {}
    for seed in args.seeds:
        control = seed in args.control_seeds
        r = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                             trace=False, t_start=time.perf_counter(),
                             control=control)
        line = {"seed": seed, "correct": r["correct"],
                "checks": {k: v["value"] for k, v in r["checks"].items()},
                "control": r.get("control"),
                "control_correct": r.get("control_correct"),
                "metrics": {k: v["value"] for k, v in r["metrics"].items()}}
        print(json.dumps(line), flush=True)
        for k, v in line["checks"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in (r.get("control") or {}).items():
            if isinstance(v, float):
                upper[k] = min(upper.get(k, float("inf")), v)
    print(json.dumps({"summary": True, "lower": lower, "upper": upper,
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
