"""Kernel micro-bench: wall time of the public ops (ref backend) plus
the bandit-step itself (the paper's per-sample decision cost).

Also benchmarks the FUSED exit epilogue (exit-norm + head matmul +
online softmax as one program) against the unfused norm-then-confidence
pair, and autotunes the fused kernel's ``block_b x block_v`` grid with
the real Pallas kernel. That needs a TPU: without one the run fails.
``--smoke`` instead runs tiny shapes anywhere, with the sweep under the
Pallas interpreter on a reduced shape; it validates every block config,
but its timings measure the interpreter, not the kernel. Every row names
the device it ran on.

    PYTHONPATH=src:. python benchmarks/kernelbench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CostModel, bandit_step, init_state
from repro.kernels.exit_confidence.ops import (exit_confidence,
                                               exit_confidence_fused)
from repro.kernels.flash_attention.ops import attention
from repro.kernels.wkv6.ops import wkv6
from repro.models.common import apply_norm

AUTOTUNE_BLOCKS_B = (32, 64, 128)
AUTOTUNE_BLOCKS_V = (256, 512, 1024)


def _time(fn, *args, iters=20, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run_fused_epilogue(key, rows, *, b, d, v, iters, smoke):
    """Fused vs unfused exit epilogue, then the block autotune sweep."""
    x = jax.random.normal(key, (b, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.02
    npar = {"scale": 1.0 + 0.1 * jax.random.normal(
        jax.random.fold_in(key, 2), (d,))}

    def unfused(x, npar, w):
        return exit_confidence(apply_norm(x, npar, "rmsnorm"), w,
                               backend="ref")

    def fused(x, npar, w):
        return exit_confidence_fused(x, npar, w, backend="ref")

    us_un = _time(jax.jit(unfused), x, npar, w, iters=iters)
    us_f = _time(jax.jit(fused), x, npar, w, iters=iters)
    rows.append(f"kernel/exit_confidence_fused/ref,{us_f:.1f},"
                f"unfused={us_un:.1f}us,speedup={us_un / us_f:.2f}x")

    # ---- block autotune: the real kernel; the interpreter under --smoke
    backend = "pallas_interpret" if smoke else "pallas"
    if smoke:                          # interpreter is slow: shrink
        b2, v2 = min(b, 8), min(v, 1024)
        x, w = x[:b2], w[:, :v2]
    tuned = []
    for bb in AUTOTUNE_BLOCKS_B:
        for bv in AUTOTUNE_BLOCKS_V:
            us = _time(exit_confidence_fused, x, npar, w, backend=backend,
                       block_b=bb, block_v=bv, iters=max(iters // 4, 1))
            tuned.append({"block_b": bb, "block_v": bv,
                          "us": round(us, 1), "backend": backend})
    best = min(tuned, key=lambda r: r["us"])
    rows.append(f"kernel/exit_confidence_fused/autotune/{backend},"
                f"{best['us']:.1f},"
                f"best_block_b={best['block_b']},"
                f"best_block_v={best['block_v']},"
                f"configs={len(tuned)}")
    return tuned, best


def run(print_csv: bool = True, smoke: bool = False, out_path: str = ""):
    dev = jax.devices()[0]
    if not smoke and dev.platform != "tpu":
        raise SystemExit(f"kernelbench times the Pallas kernels and needs a "
                         f"TPU; JAX found {dev.platform}. Use --smoke for "
                         f"the interpreter at tiny shapes.")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    rows = []
    key = jax.random.PRNGKey(0)
    iters = 3 if smoke else 20
    b, d, v = (16, 128, 2048) if smoke else (64, 768, 30522)

    # fused exit confidence: (B, D) x vocab V (the per-exit cost)
    h = jax.random.normal(key, (b, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.02
    us = _time(exit_confidence, h, w, backend="ref", iters=iters)
    gb = (h.size + w.size + b) * 4 / 1e9
    rows.append(f"kernel/exit_confidence/ref,{us:.1f},"
                f"bytes={gb:.3f}GB,eff_GBps={gb / (us / 1e6):.1f}")

    tuned, best = run_fused_epilogue(key, rows, b=b, d=d, v=v, iters=iters,
                                     smoke=smoke)

    # attention prefill (B=1, H=8, S, d=64), causal
    s = 128 if smoke else 1024
    q = jax.random.normal(key, (1, 8, s, 64))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 8, s, 64))
    v_ = jax.random.normal(jax.random.fold_in(key, 3), (1, 8, s, 64))
    us = _time(attention, q, k, v_, causal=True, backend="ref", iters=iters)
    fl = 4 * 8 * s * s * 64 / 2
    rows.append(f"kernel/flash_attention/ref,{us:.1f},"
                f"flops={fl:.2e},eff_GFLOPs={fl / (us / 1e6) / 1e9:.1f}")

    # wkv6 (B=1, H=8, T, d=64)
    t = 64 if smoke else 512
    r = jax.random.normal(key, (1, 8, t, 64))
    kk = jax.random.normal(jax.random.fold_in(key, 4), (1, 8, t, 64))
    vv = jax.random.normal(jax.random.fold_in(key, 5), (1, 8, t, 64))
    ww = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 6),
                                          (1, 8, t, 64)))
    u = jax.random.normal(jax.random.fold_in(key, 7), (8, 64))
    us = _time(wkv6, r, kk, vv, ww, u, backend="ref",
               iters=2 if smoke else 5)
    rows.append(f"kernel/wkv6/ref,{us:.1f},tokens_per_s={t / (us / 1e6):.0f}")

    # one bandit step (the paper's O(L) host-side decision)
    cost = CostModel(num_layers=12)
    state = init_state(12)
    conf_row = jnp.asarray(np.random.default_rng(0).uniform(0, 1, 12))
    us = _time(lambda s, c: bandit_step(s, c, cost=cost)[0], state,
               conf_row, iters=20 if smoke else 200)
    rows.append(f"kernel/bandit_step,{us:.1f},per_sample_decision")

    tag = f"device={dev.platform}/{dev.device_kind}"
    rows = [f"{row},{tag}" for row in rows]
    for r in tuned:
        r["device"] = dev.device_kind
    if print_csv:
        for row in rows:
            print(row)
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"benchmark": "kernelbench", "smoke": smoke,
                       "device": device,
                       "rows": rows, "fused_autotune": tuned,
                       "fused_autotune_best": best}, f, indent=2)
        print(f"wrote {out_path}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few iters for CI")
    ap.add_argument("--out", default="",
                    help="JSON artifact path ('' disables)")
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out)


if __name__ == "__main__":
    main()
