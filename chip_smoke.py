#!/usr/bin/env python3
"""Chip smoke test: SplitEE classify serving end to end on a TPU, at
elasticbert12's published widths (12 layers x d_model 768, 12 heads,
d_ff 3072, vocab 30522, one exit head per layer, 128-token inputs,
float32 weights).

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded runtime on four chips

Everything runs in this one process: a chip belongs to one process at a
time. With no TPU (or outside a checkout of the repo) it exits non-zero
and prints no result line.

One chip: train the testbed for a few dozen steps from a seed, calibrate
the exit threshold alpha, then serve the shifted evaluation stream
through `serve()` twice — with the jnp exit-confidence reference
(``conf_backend="ref"``) and with the fused Pallas exit epilogue
(``conf_backend="pallas", fused_exit=True``). Each pass serves the
batched path at B=32 over a stream whose length is not a multiple of 32,
one `Engine` push-session, SplitEE-S (``side_info=True``) and the masked
scan edge phase (``edge_mode="scan"``). Then it compares the two
backends' `edge_fn`/`cloud_fn` (and the all-exit `edge_fn_s` /
`edge_scan_fn`) confidences and predictions on the same inputs and
depths, at every pow2 bucket size 1..32.

Four chips: serve ``ServingConfig(mesh=True, replicas=4,
overlap_depth=2)`` against ``replicas=1`` on the same stream, check the
policy is the same (arms and exits), and check through
``addressable_shards`` that every device holds a shard of the batch.

The last line of stdout is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``. Any failed phase raises, so the process
exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

TRAIN_STEPS = 40
BATCH = 32
STREAM = 3 * BATCH + 11          # ragged tail of 11: bucket caps 1..16..32
SHORT_STREAM = 2 * BATCH + 7
ENGINE_REQUESTS = [5, 9, 1, 13, 8]     # request sizes of the push-session
BUCKETS = (1, 2, 4, 8, 16, 32)

# Pallas vs ref tolerances. The comparison runs at matmul precision
# "highest", so both sides take f32 products through the same layer
# stack; their exit epilogues then differ only in summation order (the
# 768-term head dot, the layernorm's reductions). That moves a logit by
# about sqrt(768) * 2**-24 * sum|h_i w_i| ~ 2e-6 * sum|h_i w_i|, and
# sum|h_i w_i| is about 20 for these heads (768 terms of |h| ~ 0.8 after
# the norm times |w| ~ 0.03 at init), far below 100: hence LOGIT_TOL.
LOGIT_TOL = 2e-4
# conf = sigmoid(top-2 margin) for a 2-class head, and d conf / d margin
# <= 1/4, so two logits each off by LOGIT_TOL move conf by at most:
CONF_TOL = 0.25 * 2 * LOGIT_TOL
# ...and can swap the argmax only where the top-2 margin is below:
MARGIN_TOL = 2 * LOGIT_TOL


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def build(cfg, *, steps: int, seq_len: int, n_eval: int = 1024):
    """Train the testbed and calibrate alpha. Returns (params, eval_data,
    cost, final train loss)."""
    import numpy as np

    from repro.core import CostModel
    from repro.launch.serve import PUBLISHED_TRAIN_BATCH, build_testbed

    _, params, _, _, eval_data, (conf_val, _), train_log = \
        build_testbed(cfg=cfg, steps=steps, seq_len=seq_len, n_train=4096,
                      n_eval=n_eval, batch_size=PUBLISHED_TRAIN_BATCH)
    # alpha at the median validation confidence over all exits, so about
    # half the samples exit and the cloud half serves the rest.
    # (`calibrate_alpha` picks 0.5 for this briefly trained testbed: every
    # 2-class confidence clears it, and nothing would reach the cloud.)
    cost = CostModel(num_layers=cfg.num_layers, offload=5.0,
                     alpha=float(np.median(conf_val)))
    return params, eval_data, cost, train_log[-1]["loss"]


def check_report(rep, n: int, cfg, what: str) -> None:
    import numpy as np
    check(rep.n == n, f"{what}: served {rep.n} of {n} samples")
    arms = np.asarray(rep.arms)
    check(len(arms) == n and arms.min() >= 0
          and arms.max() < cfg.num_layers, f"{what}: arms out of range")
    preds = np.asarray(rep.preds)
    check(len(preds) == n and preds.min() >= 0
          and preds.max() < cfg.num_classes, f"{what}: bad predictions")
    check(bool(np.isfinite(rep.cost_total)) and rep.cost_total > 0,
          f"{what}: cost {rep.cost_total}")
    check(rep.accuracy is not None and 0.0 <= rep.accuracy <= 1.0,
          f"{what}: accuracy {rep.accuracy}")
    log(f"{what}: n={rep.n} acc={rep.accuracy:.3f} "
        f"cost={rep.cost_total:.1f}λ offload_frac={rep.offload_frac:.3f} "
        f"exits/layer={np.asarray(rep.exits_per_layer).tolist()} "
        f"path={rep.path} wall={rep.wall_s:.2f}s")


def serve_pass(runtime, params, eval_data, cost, cfg, label: str) -> None:
    """The classify serving path, through its user-facing entry points."""
    from repro.data import OnlineStream
    from repro.serving import Engine, ServingConfig, serve

    stream = OnlineStream(eval_data, seed=0)
    rep = serve(runtime, params, stream, cost,
                ServingConfig(batch_size=BATCH, max_samples=STREAM))
    check_report(rep, STREAM, cfg, f"serve[{label}] batched B={BATCH}")
    check(0 < rep.offload_frac < 1,
          f"serve[{label}]: offload fraction {rep.offload_frac}; both the "
          f"early exits and the cloud half must serve")
    samples = list(itertools.islice(iter(stream), sum(ENGINE_REQUESTS)))
    eng = Engine(runtime, params, cost, ServingConfig(batch_size=8))
    it = iter(samples)
    for size in ENGINE_REQUESTS:
        eng.submit(list(itertools.islice(it, size)))
    check_report(eng.close(), len(samples), cfg,
                 f"serve[{label}] Engine B=8 push-session")
    check_report(serve(runtime, params, stream, cost,
                       ServingConfig(batch_size=BATCH, side_info=True,
                                     max_samples=SHORT_STREAM)),
                 SHORT_STREAM, cfg, f"serve[{label}] SplitEE-S B={BATCH}")
    check_report(serve(runtime, params, stream, cost,
                       ServingConfig(batch_size=BATCH, edge_mode="scan",
                                     max_samples=SHORT_STREAM)),
                 SHORT_STREAM, cfg, f"serve[{label}] scan B={BATCH}")


def agree(what: str, conf_r, pred_r, conf_k, pred_k) -> None:
    """Kernel vs reference on one output: conf within CONF_TOL, equal
    predictions wherever the reference's top-2 margin exceeds MARGIN_TOL."""
    import numpy as np
    conf_r, conf_k = np.asarray(conf_r), np.asarray(conf_k)
    pred_r, pred_k = np.asarray(pred_r), np.asarray(pred_k)
    check(bool(np.isfinite(conf_k).all()), f"{what}: non-finite kernel conf")
    dconf = float(np.max(np.abs(conf_r - conf_k)))
    c = np.clip(conf_r, 1e-7, 1 - 1e-7)
    decided = np.log(c / (1 - c)) > MARGIN_TOL
    same = pred_r == pred_k
    log(f"pallas-vs-ref {what}: max|dconf|={dconf:.3e} (tol {CONF_TOL:.0e}) "
        f"preds equal {int(same.sum())}/{same.size} "
        f"(margin>{MARGIN_TOL:.0e}: {int(decided.sum())}, all equal: "
        f"{bool(same[decided].all())})")
    check(dconf <= CONF_TOL, f"{what}: |dconf| {dconf} > {CONF_TOL}")
    check(bool(same[decided].all()),
          f"{what}: predictions differ where the margin exceeds "
          f"{MARGIN_TOL}")


def compare_backends(ref_rt, ker_rt, params, eval_data, cfg) -> None:
    """Same inputs and depths through both runtimes' device functions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    check(cfg.num_classes == 2, "the margin test reads 2-class heads")
    L = cfg.num_layers
    tokens = jnp.asarray(eval_data["tokens"][:max(BUCKETS)])
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(BUCKETS):
            depth = jnp.int32((5 * i) % L)
            batch = {"tokens": tokens[:b]}
            conf_r, pred_r, hidden = ref_rt.edge_fn(params, batch, depth)
            conf_k, pred_k, _ = ker_rt.edge_fn(params, batch, depth)
            agree(f"edge_fn B={b} depth={int(depth)}", conf_r, pred_r,
                  conf_k, pred_k)
            agree(f"cloud_fn B={b} depth={int(depth)}",
                  *ref_rt.cloud_fn(params, hidden, depth),
                  *ker_rt.cloud_fn(params, hidden, depth))
        batch = {"tokens": tokens}
        depth = jnp.int32(L - 1)
        conf_r, pred_r, _ = ref_rt.edge_fn_s(params, batch, depth)
        conf_k, pred_k, _ = ker_rt.edge_fn_s(params, batch, depth)
        agree(f"edge_fn_s B={len(tokens)} all {L} exits", conf_r, pred_r,
              conf_k, pred_k)
        depths = jnp.asarray(np.arange(len(tokens)) % L, jnp.int32)
        conf_r, pred_r, _ = ref_rt.edge_scan_fn(params, batch, depths)
        conf_k, pred_k, _ = ker_rt.edge_scan_fn(params, batch, depths)
        agree(f"edge_scan_fn B={len(tokens)} mixed depths", conf_r, pred_r,
              conf_k, pred_k)


def run_one_chip(cfg, *, seq_len: int, steps: int = TRAIN_STEPS,
                 kernel_backend: str = "pallas", clock=None) -> None:
    """Train, serve with both exit-confidence backends, compare them.
    ``kernel_backend="pallas_interpret"`` rehearses this on the CPU."""
    from repro.serving import EdgeCloudRuntime

    t0 = time.perf_counter()
    c0 = clock.total if clock else 0.0
    params, eval_data, cost, loss = build(cfg, steps=steps, seq_len=seq_len)
    log(f"testbed: {steps} train steps, final loss "
        f"{loss:.4f}, alpha={cost.alpha:.3f} "
        f"({time.perf_counter() - t0:.1f} s"
        + (f", compile {clock.total - c0:.1f} s)" if clock else ")"))
    ref_rt = EdgeCloudRuntime(cfg, conf_backend="ref")
    ker_rt = EdgeCloudRuntime(cfg, conf_backend=kernel_backend,
                              fused_exit=True)
    for label, rt in (("ref", ref_rt), (f"{kernel_backend}+fused", ker_rt)):
        t0 = time.perf_counter()
        c0 = clock.total if clock else 0.0
        serve_pass(rt, params, eval_data, cost, cfg, label)
        log(f"serve[{label}] pass: {time.perf_counter() - t0:.1f} s"
            + (f", compile {clock.total - c0:.1f} s" if clock else ""))
    compare_backends(ref_rt, ker_rt, params, eval_data, cfg)


def run_sharded(cfg, *, seq_len: int, replicas: int,
                steps: int = TRAIN_STEPS) -> None:
    """The sharded runtime on ``replicas`` devices against one replica."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import OnlineStream
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import EdgeCloudRuntime, ServingConfig, serve
    from repro.serving.sharded import _data_put

    params, eval_data, cost, loss = build(cfg, steps=steps, seq_len=seq_len)
    log(f"testbed: {steps} train steps, final loss {loss:.4f}, "
        f"alpha={cost.alpha:.3f}")
    runtime = EdgeCloudRuntime(cfg)
    stream = OnlineStream(eval_data, seed=0)
    reports = {}
    for r in (replicas, 1):
        rep = serve(runtime, params, stream, cost,
                    ServingConfig(mesh=True, replicas=r, overlap_depth=2,
                                  batch_size=BATCH, max_samples=STREAM))
        check_report(rep, STREAM, cfg, f"sharded R={r} K=2 B={BATCH}")
        check(rep.replicas == r, f"served on {rep.replicas} replicas")
        check(0 < rep.offload_frac < 1,
              f"R={r}: offload fraction {rep.offload_frac}; both the "
              f"early exits and the cloud half must serve")
        reports[r] = rep
    many, one = reports[replicas], reports[1]
    same_arms = np.array_equal(many.arms, one.arms)
    same_exits = np.array_equal(many.exited, one.exited)
    log(f"replicas={replicas} vs 1: same arms {same_arms}, same exits "
        f"{same_exits}, same preds "
        f"{np.array_equal(many.preds, one.preds)}")
    check(same_arms and same_exits, "replica count changed the policy")

    # where the work runs: a data-sharded batch through the edge program
    mesh = make_serving_mesh(replicas)
    put = _data_put(mesh)
    tokens = np.asarray(eval_data["tokens"][:BATCH])
    depth = jnp.int32(cfg.num_layers // 2)
    everywhere = jax.device_put(params, NamedSharding(mesh, P()))
    conf, _, hidden = runtime.edge_fn(everywhere, {"tokens": put(tokens)},
                                      depth)
    devices = set(mesh.devices.flat)
    for name, arr in (("edge hidden", hidden), ("edge conf", conf)):
        shards = arr.addressable_shards
        held = {s.device for s in shards}
        rows = sorted(s.data.shape[0] for s in shards)
        log(f"{name}: {len(shards)} shards on {len(held)} devices, "
            f"rows per shard {rows}")
        check(held == devices and rows == [BATCH // replicas] * replicas,
              f"{name} is not split over all {replicas} devices")
    conf_one, _, _ = runtime.edge_fn(params, {"tokens": jnp.asarray(tokens)},
                                     depth)
    dconf = float(np.max(np.abs(np.asarray(conf) - np.asarray(conf_one))))
    log(f"sharded vs one-device edge conf: max|dconf|={dconf:.3e}")
    check(dconf <= CONF_TOL, f"sharded edge conf off by {dconf}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving path (default); 4: the "
                         "sharded runtime across four chips, alone")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import PUBLISHED_SEQ_LEN, testbed_config

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2
    clock = CompileClock()
    cfg = testbed_config(published=True)
    n_params = cfg.param_count() / 1e6
    log(f"config: {cfg.arch_id} L={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"norm={cfg.norm} params={n_params:.1f}M seq_len={PUBLISHED_SEQ_LEN} "
        f"dtype={cfg.dtype} classes={cfg.num_classes}")
    log(f"device: {dev.device_kind} x{len(devices)}; compile cache "
        f"{cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(cfg, seq_len=PUBLISHED_SEQ_LEN, clock=clock)
    else:
        run_sharded(cfg, seq_len=PUBLISHED_SEQ_LEN, replicas=args.chips)
    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    log(f"done in {time.perf_counter() - t0:.1f} s, XLA compile "
        f"{clock.total:.1f} s, {entries} entries in {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
