"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2,regret,...]

Prints ``name,us_per_call,derived...`` CSV lines. The roofline section
reads dry-run JSONs if present (run repro.launch.dryrun first; it is a
separate process because it forces a 512-device topology).
"""
from __future__ import annotations

import argparse
import os

SECTIONS = ["kernels", "table2", "offload_sweep", "regret", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(SECTIONS))
    ap.add_argument("--smoke", action="store_true",
                    help="kernels section at tiny shapes under the Pallas "
                         "interpreter (without it that section needs a TPU)")
    args, _ = ap.parse_known_args()
    only = [s for s in args.only.split(",") if s] or SECTIONS

    print("name,us_per_call,derived")
    if "kernels" in only:
        from benchmarks import kernelbench
        kernelbench.run(smoke=args.smoke)
    if "table2" in only:
        from benchmarks import table2
        table2.run()
    if "offload_sweep" in only:
        from benchmarks import offload_sweep
        offload_sweep.run()
    if "regret" in only:
        from benchmarks import regret
        regret.run()
    if "roofline" in only:
        from benchmarks import roofline
        if os.path.isdir(roofline.DEFAULT_DIR) and \
                os.listdir(roofline.DEFAULT_DIR):
            roofline.run()
        else:
            print("roofline/skipped,0,no dry-run artifacts "
                  "(run: PYTHONPATH=src python -m repro.launch.dryrun --all)")


if __name__ == "__main__":
    main()
