"""Decode serving runtime: per-token SplitEE decisions during generation.

The classifier runtimes decide once per *sample*; here the bandit decides
once per *token*: every decode step draws a splitting layer from the UCB
state (eq. 1 unchanged — confidence is the exit head's max-softmax on the
step's hidden), the edge runs layers ``0..ℓ``
(``transformer.decode_step_masked``: its layer loop stops at the step's
deepest split, so layers above it are skipped, not masked; a shallower
sample's carry and cache slots stand still inside the loop), and a token
either

* **exits** at ℓ — the exit head's argmax becomes the generated token and
  layers > ℓ never advance their cache for this step (the attention ring
  buffer leaves a hole the ``pos`` mask excludes; recurrent state is a
  masked select — see serving/kvcache.py for the consistency contract), or
* **offloads** — the split-layer hidden ships through the
  :class:`OffloadCodec` round trip (the cloud computes on the
  reconstruction, so quantization loss is visible end to end; with no
  codec set, the identity, the cloud reads the edge's own device array
  and the payload never leaves the device) together with the per-step
  ≤ℓ cache-slice bytes; ``decode_step_resume`` completes layers > ℓ for
  exactly the offloaded samples (its loop starts above the
  shallowest offloaded split: the layers below are skipped, not masked)
  and its returned tree — bitwise the input everywhere it did not
  advance — re-syncs the edge cache on commit.

The cloud call blocks: unlike the classifier's deferred flush queue, step
t+1 cannot start until t's token exists — the serial dependency is
inherent to autoregressive decode, so there is nothing to overlap with.
Results come back to the host once per program: as soon as the edge (or
the cloud) is dispatched, the copies of the outputs the host decides on
are queued behind it on the device, and the host waits once, for all of
them together, in ``edge_wait`` (``cloud_wait``). The exit decision stays
on the host and reads what the programs return.

One bandit round per decode step; the communication term is per-arm (an
(L,) ``offload_scale`` — deeper splits ship strictly more cache slice).

``split_policy="final"`` forces arm L-1 every step, which makes the whole
pipeline collapse to plain full-depth generation — bit-identically
(logits, tokens, and final cache state) to the test oracle's full
``lax.scan`` sweep with every row live (the differential pin in
tests/test_decode_serving.py), and the baseline every decode benchmark
compares against. ``transformer.decode_step`` runs the same layer loop as
the two halves, over every layer with every row live.

Driven by `serving.api`: ``ServingConfig(workload="decode", ...)`` routes
`serve()`/`Engine` here; `_DecodeSession` mirrors `_BatchedSession`'s
push/drain/result contract so the scheduler and multi-tenant engine treat
both uniformly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.controller import SplitEEController
from repro.core.rewards import CostModel
from repro.data.stream import microbatches
from repro.models import transformer
from repro.serving.kvcache import (DecodeCacheManager, hidden_raw_bytes,
                                   offload_scale_vec)
from repro.serving.offload_codec import OffloadCodec
from repro.serving.tracing import Tracer

PyTree = Any


def _queue_to_host(*arrays):
    """Queue each device array's copy to the host behind the program that
    makes it, and return the arrays; the host reads them later, all in
    one `jax.device_get`."""
    for a in arrays:
        if isinstance(a, jax.Array):
            a.copy_to_host_async()
    return arrays


@dataclasses.dataclass
class DecodeRuntime:
    """Jitted prefill + edge/cloud halves of one decode-serving step.

    The decode analogue of `EdgeCloudRuntime`: `prefill_fn` builds the
    batch's caches (one retrace per (batch, prompt_len, total_len) shape),
    `edge_fn` is the masked edge pass returning every exit observable plus
    the offload payload, `cloud_fn` is the masked resume. Total sequence
    length is a static arg — the attention window depends on it.
    """
    cfg: ModelConfig
    backend: str = "ref"            # prefill kernels: ref | pallas*
    conf_backend: str = "ref"       # exit-confidence kernel

    def __post_init__(self):
        cfg = self.cfg
        if cfg.encoder is not None:
            raise NotImplementedError(
                "decode serving covers decoder-only families; enc-dec decode"
                " goes through Model.decode_step")
        if cfg.modality != "text":
            raise NotImplementedError(
                "decode serving is token-in/token-out; stub-modality archs"
                " are not supported")

        # the named scopes name each device op's program in a profiler
        # trace (metadata only: the numbers do not change)
        @jax.named_scope("splitee.prefill")
        def _prefill(params, tokens, cache_seq_len):
            return transformer.prefill(
                params, cfg, {"tokens": tokens}, backend=self.backend,
                cache_seq_len=cache_seq_len)

        @jax.named_scope("splitee.edge")
        def _edge(params, caches, token, cur_index, depths, window_seq_len):
            logits, conf, pred, hidden, new_caches = \
                transformer.decode_step_masked(
                    params, cfg, caches, token, cur_index, depths,
                    window_seq_len=window_seq_len,
                    conf_backend=self.conf_backend)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            conf_fin = jnp.max(probs, axis=-1)
            pred_fin = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (logits, conf, pred, conf_fin, pred_fin, hidden,
                    new_caches)

        @jax.named_scope("splitee.cloud")
        def _cloud(params, caches, hidden, cur_index, depths, active,
                   window_seq_len):
            logits, new_caches = transformer.decode_step_resume(
                params, cfg, caches, hidden, cur_index, depths, active,
                window_seq_len=window_seq_len)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            conf_L = jnp.max(probs, axis=-1)
            pred_L = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, conf_L, pred_L, new_caches

        self.prefill_fn = jax.jit(_prefill, static_argnums=(2,))
        self.edge_fn = jax.jit(_edge, static_argnums=(5,))
        self.cloud_fn = jax.jit(_cloud, static_argnums=(6,))


class _DecodeSession:
    """Incremental decode driver mirroring `_BatchedSession`'s contract.

    One `push(batch)` prefills the batch's prompts, then runs
    ``max_new_tokens`` decode rounds, each an independent bandit round
    (select → masked edge → per-sample exit/offload → blocking cloud
    resume for the offloaders → vectorized fold). The prefill's argmax is
    round 0's input token; generated tokens are the rounds' outputs.
    `result()` is non-destructive and adds a ``decode`` section and the
    session tracer's ``telemetry`` (serving/tracing.py), whose
    ``splitee.decode.push`` totals give ``decode_wall_s``.
    """

    def __init__(self, runtime: DecodeRuntime, params, cost: CostModel, *,
                 batch_size: int = 8, max_new_tokens: int = 1,
                 split_policy: str = "bandit", beta: float = 1.0,
                 controller_kwargs: Optional[Dict[str, Any]] = None,
                 codec: Optional[OffloadCodec] = None):
        if not isinstance(runtime, DecodeRuntime):
            raise TypeError(
                f"workload='decode' needs a DecodeRuntime, got "
                f"{type(runtime).__name__} — build one with "
                f"DecodeRuntime(cfg)")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self.runtime = runtime
        self.params = params
        self.cost = cost
        self.batch_size = batch_size
        self.max_new_tokens = max_new_tokens
        self.split_policy = split_policy
        self.codec = codec
        self.ctl = SplitEEController(cost, beta=beta,
                                     **(controller_kwargs or {}))
        # per-arm wire/raw ratio; scalar 1.0 (skipped multiply, codec-free
        # bit-identical path) when nothing is compressed
        self._scale = (offload_scale_vec(runtime.cfg, codec)
                       if codec is not None else 1.0)
        self.n = 0
        self.tracer = Tracer()
        self._pushes: List[Dict[str, Any]] = []
        self._exits_hist = np.zeros((max_new_tokens, cost.num_layers),
                                    np.int64)

    def push(self, batch):
        """Generate ``max_new_tokens`` tokens for one batch of prompts.
        Samples are dicts with an int "tokens" prompt; prompts in one push
        must share a length (pad upstream or push per length bucket)."""
        if not batch:
            return
        B = len(batch)
        try:
            prompts = np.stack(
                [np.asarray(s["tokens"], np.int32) for s in batch])
        except ValueError as e:
            raise ValueError(
                "decode push needs equal-length prompts in one batch; "
                f"got lengths {[len(s['tokens']) for s in batch]}") from e
        S = prompts.shape[1]
        T = self.max_new_tokens
        total = S + T
        cfg = self.runtime.cfg

        tr = self.tracer
        with tr.span("splitee.decode.push", push=len(self._pushes)):
            with tr.span("splitee.decode.prefill"):
                logits0, caches = self.runtime.prefill_fn(
                    self.params, jnp.asarray(prompts), total)
                mgr = DecodeCacheManager(cfg, caches, codec=self.codec)
                tok = jnp.argmax(logits0, -1).astype(jnp.int32)
            gen = np.zeros((B, T), np.int32)
            exited_steps = np.zeros((T, B), bool)
            for t in range(T):
                with tr.span("splitee.decode.step", push=len(self._pushes),
                             step=t):
                    tok = self._round(mgr, tok, S + t, total, t, gen,
                                      exited_steps)

        self.n += B * T
        self._pushes.append({
            "tokens": gen,
            "prompt_len": S,
            "realized_depths": np.stack(mgr.realized_depths, 0).T,  # (B, T)
            "exited_steps": exited_steps.T,                         # (B, T)
            "offloaded_steps": np.stack(mgr.offloaded, 0).T,        # (B, T)
            "offloads_per_seq": mgr.offloads_per_seq,
            "wire_bytes_per_seq": mgr.wire_bytes_per_seq,
        })

    def _round(self, mgr: DecodeCacheManager, tok, step: int, total: int,
               t: int, gen: np.ndarray, exited_steps: np.ndarray):
        """One token round at position ``step``: select → masked edge →
        per-row exit/offload → blocking cloud resume → fold. Writes round
        ``t``'s tokens and exits into ``gen``/``exited_steps`` and returns
        the next round's input token on the device.

        Each program's host-bound outputs (the exit confidences and
        tokens) have their copies queued at its dispatch and are read in
        one fetch, so the host waits once per program. The offload payload
        goes to the cloud as the edge's ``hidden`` device array under the
        identity codec; only a real codec reads it on the host (its copy
        queued with the edge's outputs) and uploads the decoded rows. The
        tracer's ``splitee.decode.host_fetches`` counts the blocking
        fetches."""
        tr = self.tracer
        B = gen.shape[0]
        L = self.cost.num_layers
        tr.count("splitee.decode.steps")
        with tr.span("splitee.decode.select"):
            if self.split_policy == "final":
                arms = np.full(B, L - 1, np.int64)
            else:
                arms = np.asarray(self.ctl.choose_splits(B), np.int64)
            depths_dev = jnp.asarray(arms, jnp.int32)
        # layers each program's loop runs: the edge's stops at the deepest
        # split, the cloud's starts above the shallowest offloaded one; and
        # the cache state those layers carry
        hi = int(arms.max()) + 1
        tr.count("splitee.decode.edge_layers", hi)
        tr.count("splitee.decode.state_bytes", int(mgr.layer_bytes[:hi].sum()))
        with tr.span("splitee.decode.edge"):
            (_, conf_all, pred_all, conf_fin, pred_fin, hidden,
             new_caches) = self.runtime.edge_fn(
                self.params, mgr.caches, tok, step, depths_dev, total)
            mgr.commit_edge(new_caches, arms)
            edge_out = _queue_to_host(conf_all, pred_all, conf_fin, pred_fin)
            if mgr.codec is not None:
                _queue_to_host(hidden)
        with tr.span("splitee.decode.edge_wait"):
            tr.count("splitee.decode.host_fetches")
            conf_np, pred_np, conf_fin_np, pred_fin_np = jax.device_get(
                edge_out)                             # conf, pred: (L, B)

        # at the final arm there is no split: confidence and token come
        # from the LM head itself, so forced-final decode IS plain
        # full-depth generation
        with tr.span("splitee.decode.decide"):
            conf_paths: List[np.ndarray] = []
            toks_next = np.empty(B, np.int32)
            offload_rows: List[int] = []
            for b in range(B):
                arm = int(arms[b])
                ci = (float(conf_fin_np[b]) if arm + 1 == L
                      else float(conf_np[arm, b]))
                conf_paths.append(np.asarray([ci], np.float64))
                if ci >= self.cost.alpha or arm + 1 == L:
                    toks_next[b] = (pred_fin_np[b] if arm + 1 == L
                                    else pred_np[arm, b])
                else:
                    offload_rows.append(b)

        if offload_rows:
            tr.count("splitee.decode.cloud_launches")
            tr.count("splitee.decode.offload_rows", len(offload_rows))
            lo = int(arms[offload_rows].min()) + 1
            tr.count("splitee.decode.cloud_layers", L - lo)
            tr.count("splitee.decode.state_bytes",
                     int(mgr.layer_bytes[lo:].sum()))
            with tr.span("splitee.decode.codec"):
                rows = np.asarray(offload_rows, np.int64)
                if mgr.codec is None:
                    # the identity codec: the cloud reads the edge's own
                    # payload, which never leaves the device
                    hid_dev, hid_wire = hidden, hidden_raw_bytes(mgr.cfg)
                else:
                    tr.count("splitee.decode.host_fetches")
                    hidden_np = np.asarray(hidden)
                    dec_rows, hid_wire = mgr.ship_hidden(hidden_np, rows)
                    hid_in = hidden_np.copy()
                    hid_in[rows] = dec_rows
                    hid_dev = jnp.asarray(hid_in)
                active = np.zeros(B, bool)
                active[rows] = True
                active_dev = jnp.asarray(active)
            with tr.span("splitee.decode.cloud"):
                _, conf_L_d, pred_L_d, new_caches = self.runtime.cloud_fn(
                    self.params, mgr.caches, hid_dev, step, depths_dev,
                    active_dev, total)
                mgr.commit_cloud(new_caches, active)
                cloud_out = _queue_to_host(conf_L_d, pred_L_d)
            with tr.span("splitee.decode.cloud_wait"):
                tr.count("splitee.decode.host_fetches")
                conf_L_np, pred_L_np = jax.device_get(cloud_out)

        with tr.span("splitee.decode.fold"):
            conf_Ls: List[Optional[float]] = [None] * B
            obs: List[int] = [0] * B
            if offload_rows:
                bytes_rows = mgr.meter(rows, arms, hid_wire)
                tr.count("splitee.decode.offload_bytes", int(bytes_rows.sum()))
                for j, b in enumerate(rows):
                    conf_Ls[b] = float(conf_L_np[b])
                    obs[b] = int(bytes_rows[j])
                    toks_next[b] = pred_L_np[b]
            else:
                mgr.note_no_offload()
            exited = np.asarray(self.ctl.update_batch(
                arms, conf_paths, conf_Ls, obs,
                offload_scale=self._scale), bool)
            self._exits_hist[t] += np.bincount(arms[exited], minlength=L)
            exited_steps[t] = exited
            gen[:, t] = toks_next
            return jnp.asarray(toks_next)

    def drain(self):
        """The cloud resume blocks inside push — nothing is in flight."""

    def result(self) -> Dict[str, Any]:
        ctl = self.ctl
        hist = {k: np.asarray(v) for k, v in ctl.history.items()}
        tot = ctl.totals
        T = self.max_new_tokens
        seqs = sum(p["tokens"].shape[0] for p in self._pushes)
        telemetry = self.tracer.snapshot()
        push = telemetry["spans"].get("splitee.decode.push")
        wall = push["total_ms"] / 1e3 if push else 0.0

        def cat(key):
            if not self._pushes:
                return np.zeros((0, T) if key != "offloads_per_seq"
                                and key != "wire_bytes_per_seq"
                                else (0,), np.int64)
            return np.concatenate([p[key] for p in self._pushes], 0)

        out = {
            "n": self.n,
            "batch_size": self.batch_size,
            # one pred per bandit round, step-major like the fold order
            "preds": (np.concatenate(
                [p["tokens"].T.reshape(-1) for p in self._pushes])
                if self._pushes else np.zeros(0, np.int32)),
            "cost_total": float(tot["cost"]),
            "offload_frac": (1.0 - tot["exited"] / tot["served"]
                             if tot["served"] else 0.0),
            "offload_bytes": int(tot["offload_bytes"]),
            "arms": hist["arm"],
            "rewards": hist["reward"],
            "exited": hist["exited"],
            "state": ctl.snapshot(),
            "decode": {
                "max_new_tokens": T,
                "split_policy": self.split_policy,
                "sequences": seqs,
                "tokens_generated": seqs * T,
                "decode_wall_s": wall,
                "tokens_per_sec": seqs * T / wall if wall > 0 else 0.0,
                "exits_per_layer_per_step": self._exits_hist.copy(),
                "tokens": cat("tokens"),
                "realized_depths": cat("realized_depths"),
                "exited_steps": cat("exited_steps"),
                "offloaded_steps": cat("offloaded_steps"),
                "offloads_per_sequence": cat("offloads_per_seq"),
                "wire_bytes_per_sequence": cat("wire_bytes_per_seq"),
            },
            "telemetry": telemetry,
        }
        return out


def _serve_stream_decode(runtime: DecodeRuntime, params, stream,
                         cost: CostModel, *, batch_size: int = 8,
                         max_new_tokens: int = 1,
                         split_policy: str = "bandit", beta: float = 1.0,
                         max_samples: int = 0,
                         controller_kwargs: Optional[Dict[str, Any]] = None,
                         codec: Optional[OffloadCodec] = None,
                         ) -> Dict[str, Any]:
    """Offline driver: replay a finite prompt stream through a decode
    session (the `serve()` facade's workload="decode" entrypoint)."""
    sess = _DecodeSession(runtime, params, cost, batch_size=batch_size,
                          max_new_tokens=max_new_tokens,
                          split_policy=split_policy, beta=beta,
                          controller_kwargs=controller_kwargs, codec=codec)
    for batch in microbatches(stream, batch_size, max_samples):
        sess.push(batch)
    sess.drain()
    return sess.result()
