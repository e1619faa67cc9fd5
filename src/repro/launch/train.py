"""Training driver: jitted train_step (loss + grads + AdamW) and a small
CPU-runnable main for the multi-exit training used by the paper
experiments. The same train_step is what the multi-pod dry-run lowers.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.data import batch_iterator, make_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import Model, build_model
from repro.optim import adamw_init, adamw_update
from repro.optim.adamw import AdamWConfig
from repro.optim.schedules import cosine_schedule


def make_train_step(model: Model, opt_cfg: AdamWConfig, *,
                    total_steps: int = 1000, warmup: int = 50,
                    remat: bool = True):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.train_loss(p, batch, remat=remat))(params)
        lr_scale = cosine_schedule(opt_state["count"], total_steps, warmup)
        new_params, new_opt, gnorm = adamw_update(
            params, grads, opt_state, opt_cfg, lr_scale)
        return new_params, new_opt, {"loss": loss, "gnorm": gnorm}

    return train_step


def train_classifier(cfg, data: Dict[str, np.ndarray], *, steps: int,
                     batch_size: int, seed: int = 0,
                     lr: float = 3e-4, log_every: int = 20,
                     eval_data=None, remat: bool = False):
    """Train a multi-exit classifier (the paper's supervised fine-tune
    stage ii). Returns (params, model, log)."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    opt_cfg = AdamWConfig(lr=lr)
    opt_state = adamw_init(params)
    step_fn = jax.jit(make_train_step(model, opt_cfg, total_steps=steps,
                                      remat=remat))
    log = []
    it = batch_iterator(data, batch_size, seed=seed, epochs=10_000)
    t0 = time.time()
    for step in range(steps):
        b = next(it)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "labels": jnp.asarray(b["labels"])}
        params, opt_state, info = step_fn(params, opt_state, batch)
        if step % log_every == 0 or step == steps - 1:
            log.append({"step": step, "loss": float(info["loss"]),
                        "time": time.time() - t0})
    return params, model, log


def exit_accuracy(model: Model, params, data, *, batch_size: int = 256):
    """Per-exit accuracy + confidence on a dataset (diagnostics + SplitEE
    input). Returns conf (N, L), pred (N, L), correct (N, L)."""
    confs, preds = [], []
    n = len(data["labels"])
    for s in range(0, n, batch_size):
        batch = {"tokens": jnp.asarray(data["tokens"][s:s + batch_size])}
        out = model.forward_exits(params, batch)
        confs.append(np.asarray(out["conf"]).T)     # (B, L)
        preds.append(np.asarray(out["pred"]).T)
    conf = np.concatenate(confs)
    pred = np.concatenate(preds)
    correct = pred == data["labels"][:n, None]
    return conf, pred, correct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="elasticbert12")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--domain", default="sst2_like")
    ap.add_argument("--n-train", type=int, default=8192)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.num_classes == 0:
        raise SystemExit("train.py main targets classification testbeds; "
                         "use examples/ for LM training")
    from repro.data.synthetic import DOMAINS, VOCAB
    cfg = dataclasses.replace(cfg, vocab_size=VOCAB,
                              num_classes=DOMAINS[args.domain].num_classes,
                              dtype="float32")
    data = make_dataset(args.domain, args.n_train, seed=0)
    params, model, log = train_classifier(
        cfg, data, steps=args.steps, batch_size=args.batch_size)
    for row in log:
        print(f"step {row['step']:5d} loss {row['loss']:.4f} "
              f"t={row['time']:.1f}s")


if __name__ == "__main__":
    main()
