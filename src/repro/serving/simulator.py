"""Edge/cloud split-computing runtime (the paper's Figure 1, executable).

Two separately-jitted device functions model the two halves of the split:

  edge_fn(params, tokens, depth)  — embeds + layers 1..depth + the exit at
      `depth` (fused confidence). Runs with a *dynamic* depth via
      ``lax.fori_loop`` so one compilation serves every splitting layer —
      exactly the paper's observation that each transformer layer reuses
      the same hardware module.
  cloud_fn(params, hidden, depth) — layers depth+1..L + final head.

The offload payload between them is the layer-`depth` activation
(B, S, D) — its byte size is metered per sample and is what the paper's
`o` abstracts (and what the pod-axis transfer realizes in the multi-pod
mapping).

SplitEE-S additionally reads the exits *below* depth; the runtime exposes
``edge_fn_s`` returning the full (depth-masked) confidence vector.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.controller import SplitEEController
from repro.core.rewards import CostModel
from repro.kernels.exit_confidence.ops import (exit_confidence,
                                               exit_confidence_fused)
from repro.models.common import apply_norm
from repro.models.transformer import (_exit_w, _layer_full, _positions,
                                      embed_inputs, forward_exits_masked,
                                      pool_hidden, stacked_exit_confidence)
from repro.serving.offload_codec import OffloadCodec


@dataclasses.dataclass
class EdgeCloudRuntime:
    cfg: ModelConfig
    backend: str = "ref"
    # backend for the exit-confidence decision op ("ref" | "pallas" |
    # "pallas_interpret") and whether to run it as the fused epilogue
    # (norm + head + online softmax in one program) instead of the
    # unfused apply_norm -> exit_confidence pair
    conf_backend: str = "ref"
    fused_exit: bool = False

    def __post_init__(self):
        cfg = self.cfg
        backend = self.backend
        conf_backend = self.conf_backend
        fused_exit = self.fused_exit

        def run_layers(params, x, positions, start, stop):
            def body(i, xx):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                xx, _ = _layer_full(cfg, params, lp, xx, positions, i,
                                    window=0, backend=backend)
                return xx
            return jax.lax.fori_loop(start, stop, body, x)

        def exit_at(params, x, depth):
            """Exit observables at 1-indexed layer = depth (0-idx arm)."""
            lp = jax.tree.map(lambda a: a[depth], params["layers"])
            w = _exit_w(params, lp)
            if fused_exit:
                # pooling commutes with the per-token norm, so the fused
                # epilogue takes the raw pooled hidden
                return exit_confidence_fused(pool_hidden(cfg, x),
                                             lp["exit_norm"], w,
                                             kind=cfg.norm,
                                             backend=conf_backend)
            hn = apply_norm(x, lp["exit_norm"], cfg.norm)
            pooled = pool_hidden(cfg, hn)
            return exit_confidence(pooled, w, backend=conf_backend)

        @jax.jit
        def edge_fn(params, batch, depth):
            """Layers 1..depth+1 (depth is the 0-indexed arm)."""
            x = embed_inputs(params, cfg, batch)
            b, s, _ = x.shape
            pos = _positions(cfg, b, s)
            x = run_layers(params, x, pos, 0, depth + 1)
            conf, pred = exit_at(params, x, depth)
            return conf, pred, x

        @jax.jit
        def cloud_fn(params, hidden, depth):
            b, s, _ = hidden.shape
            pos = _positions(cfg, b, s)
            x = run_layers(params, hidden, pos, depth + 1, cfg.num_layers)
            lp_last = jax.tree.map(lambda a: a[-1], params["layers"])
            xf = apply_norm(x, params["final_norm"], cfg.norm)
            pooled = pool_hidden(cfg, xf)
            w = _exit_w(params, lp_last)
            return exit_confidence(pooled, w, backend=conf_backend)

        @jax.jit
        def edge_fn_s(params, batch, depth):
            """SplitEE-S edge pass: confidences of ALL exits <= depth.
            (Simulated with a full scan + mask — the *cost model* still
            charges only depth layers; see core.rewards.)"""
            x = embed_inputs(params, cfg, batch)
            b, s, _ = x.shape
            pos = _positions(cfg, b, s)

            def body(carry, inp):
                xx = carry
                lp, i = inp
                xx2, _ = _layer_full(cfg, params, lp, xx, pos, i,
                                     window=0, backend=backend)
                xx = jnp.where(i <= depth, xx2, xx)
                src = xx if fused_exit else apply_norm(
                    xx, lp["exit_norm"], cfg.norm)
                return xx, pool_hidden(cfg, src)

            idx = jnp.arange(cfg.num_layers)
            x, pooled = jax.lax.scan(body, x, (params["layers"], idx))
            conf, pred = stacked_exit_confidence(
                params, cfg, pooled, conf_backend=conf_backend,
                fused_exit=fused_exit)
            return conf, pred, x

        @jax.jit
        def edge_scan_fn(params, batch, depths):
            """Masked scan edge pass: one program per batch *shape*.

            `depths` is a per-sample (B,) vector of 0-indexed arms; the
            scan carry freezes each row at its own depth, so `hidden`
            is the per-sample offload payload and conf/pred hold every
            exit's observables (serving slices per sample host-side).
            Unlike `edge_fn`, the compiled program does not depend on
            the depth values at all — only on the batch shape."""
            out = forward_exits_masked(params, cfg, batch, depths,
                                       backend=backend, window=0,
                                       conf_backend=conf_backend,
                                       fused_exit=fused_exit)
            return out["conf"], out["pred"], out["hidden"]

        self.edge_fn = edge_fn
        self.cloud_fn = cloud_fn
        self.edge_fn_s = edge_fn_s
        self.edge_scan_fn = edge_scan_fn

    def offload_bytes(self, batch_size: int, seq_len: int) -> int:
        return batch_size * seq_len * self.cfg.d_model \
            * jnp.dtype(self.cfg.dtype).itemsize


def _serve_stream_sequential(runtime: EdgeCloudRuntime, params, stream,
                             cost: CostModel, *, side_info: bool = False,
                             beta: float = 1.0, max_samples: int = 0,
                             labels_for_accounting: bool = True,
                             controller_kwargs: Optional[Dict[str, Any]] = None,
                             codec: Optional[OffloadCodec] = None,
                             ) -> Dict[str, Any]:
    """Stream samples through the online SplitEE controller + edge/cloud
    runtime. Unsupervised: labels (if present) are used only for reporting.

    With a ``codec``, the offload payload is encoded/decoded at the
    edge->cloud handoff (the cloud sees the lossy reconstruction) and both
    the byte accounting and the bandit's communication cost use the wire
    bytes actually shipped.
    """
    cfg = runtime.cfg
    ctl = SplitEEController(cost, beta=beta, side_info=side_info,
                            **(controller_kwargs or {}))
    correct, preds = [], []
    n = 0
    for sample in stream:
        tokens = jnp.asarray(sample["tokens"])[None, :]
        batch = {"tokens": tokens}
        arm = ctl.choose_split()
        if side_info:
            conf_all, pred_all, hidden = runtime.edge_fn_s(
                params, batch, jnp.int32(arm))
            conf_path = np.asarray(conf_all[: arm + 1, 0])
            pred_i = int(pred_all[arm, 0])
        else:
            conf, pred_v, hidden = runtime.edge_fn(params, batch,
                                                   jnp.int32(arm))
            conf_path = np.asarray(conf)
            pred_i = int(pred_v[0])
        conf_i = float(conf_path[-1])
        will_exit = (conf_i >= cost.alpha) or (arm + 1 == cost.num_layers)
        conf_L = None
        ob = 0
        # scale applies to the communication term of EVERY arm's reward
        # (counterfactual offloads ship through the same codec), so it
        # depends only on the codec + shape, not on this sample's decision
        scale = (1.0 if codec is None else
                 codec.cost_ratio(tokens.shape[1], cfg.d_model,
                                  jnp.dtype(cfg.dtype).itemsize))
        if not will_exit:
            if codec is None:
                ob = runtime.offload_bytes(1, tokens.shape[1])
            else:
                enc = codec.encode(np.asarray(hidden))
                hidden = jnp.asarray(codec.decode(enc))
                ob = enc.row_bytes
            conf_L_v, pred_L = runtime.cloud_fn(params, hidden,
                                                jnp.int32(arm))
            conf_L = float(conf_L_v[0])
            pred_i = int(pred_L[0])
        ctl.update(arm, conf_path, conf_L,
                   offload_bytes=0 if will_exit else ob,
                   offload_scale=scale)
        preds.append(pred_i)
        if labels_for_accounting and "labels" in sample:
            correct.append(int(pred_i == int(sample["labels"])))
        n += 1
        if max_samples and n >= max_samples:
            break
    hist = {k: np.asarray(v) for k, v in ctl.history.items()}
    tot = ctl.totals
    out = {
        "n": n,
        "batch_size": 1,       # keeps the report shape uniform across paths
        "preds": np.asarray(preds),
        # scalar accounting from the controller's O(1) aggregates, so
        # record_history=False long streams still report correctly
        "cost_total": float(tot["cost"]),
        "offload_frac": (1.0 - tot["exited"] / tot["served"]
                         if tot["served"] else 0.0),
        "offload_bytes": int(tot["offload_bytes"]),
        "arms": hist["arm"],
        "rewards": hist["reward"],
        "exited": hist["exited"],
        "state": ctl.snapshot(),
    }
    if correct:
        out["accuracy"] = float(np.mean(correct))
    return out


def serve_stream(runtime: EdgeCloudRuntime, params, stream, cost: CostModel,
                 *, side_info: bool = False, beta: float = 1.0,
                 max_samples: int = 0, labels_for_accounting: bool = True):
    """Deprecated: build a `ServingConfig(path="sequential", ...)` and
    call `repro.serving.serve` instead. Returns the facade's
    `ServeReport` (dict-compatible with the legacy result)."""
    from repro.serving.api import ServingConfig, _warn_legacy, serve
    _warn_legacy("serve_stream")
    config = ServingConfig(path="sequential", side_info=side_info,
                           beta=beta, max_samples=max_samples,
                           labels_for_accounting=labels_for_accounting)
    return serve(runtime, params, stream, cost, config)
